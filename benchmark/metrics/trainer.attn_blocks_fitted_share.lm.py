"""Percent of the window's layer-sequences whose attention ran the
library's splash kernels at tile sizes FITTED to the call (the mask's kind
and reach, the length, q . k's and v's lanes, the query heads a group:
`model.attention_blocks`, the rule `model._splash` chooses by) and not at
512 in all eight fields: counters `LM_ATTN_BLOCKS_FITTED` over
`LM_ATTN_BLOCKS_FITTED` + `LM_ATTN_BLOCKS_PLAIN` (one a layer a sequence
whose attention is `model.attention_core`'s on a TPU, the multi-token
module's layer too, `PSLMTrainer._count_stats`, by that same function:
`model.attention_blocks_name`), measured window. 100 where every kind of
layer of the cell is in the rule's table, 0 where each kept 512 because no
other size was measured to win, between where a cell's kinds differ; a
delta layer and `keye30b.ps-16k`'s selected attention count neither. A
program that has no such counters (the parent commit of PR 62, where every
kernel ran at 512) gives nothing."""

from benchmark.lib import counters


def read(obs):
    return counters.share(obs.window.counters, "LM_ATTN_BLOCKS_FITTED",
                          "LM_ATTN_BLOCKS_PLAIN")

"""Device milliseconds a step in the attention over a selection (norm,
projections, the heads' q and k norms, the sectioned rotary, the attention
proper over the selected keys, output projection), forward and backward:
scopes `mv.lm.attn.sparse` and `mv.lm.attn.sparse.kernel`, busiest chip,
traced window. The indexer, the search and the divergence are not in it
(`trainer.indexer_ms_per_step.lm`, `trainer.select_ms_per_step.lm`)."""

from benchmark.lib import lmshapes

SCOPES = ("mv.lm.attn.sparse", "mv.lm.attn.sparse.kernel")


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, SCOPES)

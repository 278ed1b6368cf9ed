"""The indexer's share of the bf16 peak, percent: its projections, the
index scores of every causal pair forward and of the selected pairs
backward, and the divergence's target over the selected pairs
(benchmark/lib/sparseshapes.py `indexer_flops`; the recomputed forward
pass not counted), over the device time under `mv.lm.indexer`,
`mv.lm.indexer.loss` AND `mv.lm.select`: the scores that the search reads
are computed where the search runs, so its time is in the denominator
and the share reads low by the search's own work. Compute-bound."""

from benchmark.lib import lmshapes, sparseshapes

SCOPES = ("mv.lm.indexer", "mv.lm.indexer.loss", "mv.lm.select")


def read(obs):
    took = lmshapes.scopes_seconds(obs, SCOPES)
    if not took or "index_topk" not in obs.shapes:
        return None
    s = obs.shapes
    flops = obs.traced.rounds * s["layers"] * sparseshapes.indexer_flops(s)
    return lmshapes.share_of_peak(flops, took, obs.peaks["bf16_flops_per_s"])

"""The sparse attention's share of the bf16 peak, percent: the operations
of the SELECTED (query, key) pairs alone, `sum_t min(t + 1, topk)` a head
a layer, forward and backward (benchmark/lib/sparseshapes.py; the
recomputed forward pass is not counted), over the device time under
`mv.lm.attn.sparse.kernel`. Counted from the model's work whatever
implements it: a kernel that computes every causal tile under the
selection does four times the selected pairs at 16,384 positions and
reads low here. Compute-bound."""

from benchmark.lib import lmshapes, sparseshapes

SCOPES = ("mv.lm.attn.sparse.kernel",)


def read(obs):
    took = lmshapes.scopes_seconds(obs, SCOPES)
    if not took or "index_topk" not in obs.shapes:
        return None
    s = obs.shapes
    flops = obs.traced.rounds * s["layers"] * sparseshapes.attention_flops(s)
    return lmshapes.share_of_peak(flops, took, obs.peaks["bf16_flops_per_s"])

"""The latent attention kernel's share of the bf16 peak, percent: the
operations of the causal (query, key) pairs alone, `2 (192 + 128)` a pair
a held head (the published widths: scores over 192 lanes, the product
with v over 128), forward and twice for backward
(benchmark/lib/mlashapes.py; the recomputed forward pass and the kernel's
own recomputation of the scores are not counted), over the device time
under `mv.lm.attn.mla.kernel`. The kernel is the library's splash
attention at 192-wide q and k beside 128-wide v. Compute-bound."""

from benchmark.lib import lmshapes, mlashapes

SCOPES = ("mv.lm.attn.mla.kernel",)


def read(obs):
    took = lmshapes.scopes_seconds(obs, SCOPES)
    if not took or "heads_held" not in obs.shapes:
        return None
    s = obs.shapes
    flops = obs.traced.rounds * mlashapes.blocks(s) \
        * mlashapes.attention_flops(s)
    return lmshapes.share_of_peak(flops, took, obs.peaks["bf16_flops_per_s"])

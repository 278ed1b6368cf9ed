"""The attention kernels' share of the bf16 peak in a model whose layers
are of two kinds, percent: each layer's unmasked (query, key) pairs (causal,
or the window's) x `2 (128 + 128)` operations a pair x that kind's own
query heads, forward and twice for backward (benchmark/lib/mixedshapes.py;
the recomputed forward pass and the kernel's own recomputation of the
scores are not counted), over the device time under both kinds' kernel
scopes, `mv.lm.attn.full.kernel` and `mv.lm.attn.window.kernel`. The
kernel is the library's splash attention. Compute-bound."""

from benchmark.lib import lmshapes, mixedshapes

SCOPES = ("mv.lm.attn.full.kernel", "mv.lm.attn.window.kernel")


def read(obs):
    took = lmshapes.scopes_seconds(obs, SCOPES)
    if not took or "heads_layout" not in obs.shapes:
        return None
    flops = obs.traced.rounds * mixedshapes.attention_flops(obs.shapes)
    return lmshapes.share_of_peak(flops, took, obs.peaks["bf16_flops_per_s"])

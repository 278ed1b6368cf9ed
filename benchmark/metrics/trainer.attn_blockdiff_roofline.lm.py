"""The attention kernel's share of the bf16 peak under the
block-diffusion mask, percent: the operations of the unmasked (query,
key) pairs alone, `B (L^2 + L b)` a head a layer, forward and backward
(benchmark/lib/bdshapes.py; the recomputed forward pass and the kernel's
own recomputation of the scores are not counted), over the device time
under `mv.lm.attn.blockdiff.kernel`. The kernel is the library's splash
attention under the program's own mask object. Compute-bound."""

from benchmark.lib import bdshapes, lmshapes

SCOPES = ("mv.lm.attn.blockdiff.kernel",)


def read(obs):
    took = lmshapes.scopes_seconds(obs, SCOPES)
    if not took or "block_length" not in obs.shapes:
        return None
    s = obs.shapes
    flops = obs.traced.rounds * s["layers"] * bdshapes.attention_flops(
        s["sequences"], s["seq_len"], s["heads"], s["head_dim"],
        s["block_length"])
    return lmshapes.share_of_peak(flops, took, obs.peaks["bf16_flops_per_s"])

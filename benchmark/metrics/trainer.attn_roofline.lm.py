"""The attention kernels' share of the bf16 peak, percent: the
operations of the unmasked (query, key) pairs alone, forward and
backward (benchmark/lib/lmshapes.py; the recomputed forward pass and
the kernel's own recomputation of the scores are not counted), over the
device time under `mv.lm.attn.full.kernel` and
`mv.lm.attn.window.kernel`. The kernel is the library's
(jax.experimental.pallas.ops.tpu splash attention). Compute-bound."""

from benchmark.lib import lmshapes

SCOPES = ("mv.lm.attn.full.kernel", "mv.lm.attn.window.kernel")


def read(obs):
    took = lmshapes.scopes_seconds(obs, SCOPES)
    if not took:
        return None
    s = obs.shapes
    flops = obs.traced.rounds * sum(
        lmshapes.attention_flops(s["sequences"], s["seq_len"], s["heads"],
                                 s["head_dim"], s["window"] if w else 0)
        for w in s["window_layout"])
    return lmshapes.share_of_peak(flops, took, obs.peaks["bf16_flops_per_s"])

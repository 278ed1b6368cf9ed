"""Model FLOP/s utilization of the tenth family's measured window, percent
of peaks.json's `bf16_flops_per_s`: the operations the window's steps require
(benchmark/lib/ssdshapes.py, BY LAYER KIND: a state-space layer's two
products and its scan as the function defines it at the chunk that ran, the
attention layer's projections and its attention over the causal pairs at 64 |
64 lanes, every layer's dense MLP, the head over the one table; backward at
twice the forward, nothing made again) over the window's seconds. The share
of the WHOLE step, not a kernel's roofline share: idle time is in it."""

from benchmark.lib import lmshapes, ssdshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window, ("LM_STEP",))
    if counts is None or "ssd_heads" not in obs.shapes:
        return None
    return lmshapes.share_of_peak(
        ssdshapes.step_flops(counts[0], obs.shapes), obs.window.seconds,
        obs.peaks["bf16_flops_per_s"])

"""Model FLOP/s utilization of the ninth family's measured window, percent
of peaks.json's `bf16_flops_per_s`: the operations the window's steps
require (benchmark/lib/convshapes.py, BY LAYER KIND: a convolution layer's
two products and its chain, an attention layer's projections and its
attention over the causal pairs at 64 | 64 lanes, the dense MLPs, routers,
the head over the one table, the experts' products for the assignments the
counter `LM_HELD_ASSIGNMENTS` saw; backward at twice the forward, nothing
made again) over the window's seconds. The share of the WHOLE step, not a
kernel's roofline share: idle time is in it."""

from benchmark.lib import convshapes, lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window,
                                    ("LM_STEP", "LM_HELD_ASSIGNMENTS"))
    if counts is None or "conv_taps" not in obs.shapes:
        return None
    flops = convshapes.step_flops(counts[0], counts[1], obs.shapes)
    return lmshapes.share_of_peak(flops, obs.window.seconds,
                                  obs.peaks["bf16_flops_per_s"])

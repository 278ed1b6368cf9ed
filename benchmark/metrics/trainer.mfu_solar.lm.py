"""Model FLOP/s utilization of the eighth family's measured window, percent
of peaks.json's `bf16_flops_per_s`: the operations the window's steps
require (benchmark/lib/solarshapes.py, BY LAYER KIND over the HELD heads: a
delta layer's projections, convolutions and recurrence, a softmax layer's
projections with its lane gate and its attention over the causal pairs,
routers and shared experts, the head, the experts' products for the
assignments the counter `LM_HELD_ASSIGNMENTS` saw; backward at twice the
forward, nothing made again) over the window's seconds. The share of the
WHOLE step, not a kernel's roofline share: idle time is in it."""

from benchmark.lib import lmshapes, solarshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window,
                                    ("LM_STEP", "LM_HELD_ASSIGNMENTS"))
    if counts is None or "heads_all" not in obs.shapes:
        return None
    flops = solarshapes.step_flops(counts[0], counts[1], obs.shapes)
    return lmshapes.share_of_peak(flops, obs.window.seconds,
                                  obs.peaks["bf16_flops_per_s"])

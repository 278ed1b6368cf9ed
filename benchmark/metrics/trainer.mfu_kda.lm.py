"""Model FLOP/s utilization of the sixth family's measured window, percent
of peaks.json's `bf16_flops_per_s`: the operations the window's steps
require (benchmark/lib/kdashapes.py, BY LAYER KIND: a delta layer's
projections, convolutions and recurrence, a latent layer's projections and
attention over the causal pairs at 192 + 128 lanes a head, dense MLP,
routers and shared experts, the head, the experts' products for the
assignments the counter `LM_HELD_ASSIGNMENTS` saw; backward at twice the
forward, nothing made again) over the window's seconds. An end-to-end
utilization, not a kernel's roofline share: idle time is in it."""

from benchmark.lib import kdashapes, lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window,
                                    ("LM_STEP", "LM_HELD_ASSIGNMENTS"))
    if counts is None or "kda_heads" not in obs.shapes:
        return None
    flops = kdashapes.step_flops(counts[0], counts[1], obs.shapes)
    return lmshapes.share_of_peak(flops, obs.window.seconds,
                                  obs.peaks["bf16_flops_per_s"])

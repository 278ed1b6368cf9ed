"""Model FLOP/s utilization of the fourth family's measured window,
percent of peaks.json's `bf16_flops_per_s`: the operations the window's
steps require (benchmark/lib/mixedshapes.py: every layer's projections and
gate over its own heads, the dense MLP, routers and shared experts, the
head, attention over each kind's unmasked pairs, the experts' products
for the assignments the counter `LM_HELD_ASSIGNMENTS` saw; backward at
twice the forward, the recomputed layer not counted) over the window's
seconds. An end-to-end utilization, not a kernel's roofline share: idle
time is in it."""

from benchmark.lib import lmshapes, mixedshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window,
                                    ("LM_STEP", "LM_HELD_ASSIGNMENTS"))
    if counts is None or "heads_layout" not in obs.shapes:
        return None
    flops = mixedshapes.step_flops(counts[0], counts[1], obs.shapes)
    return lmshapes.share_of_peak(flops, obs.window.seconds,
                                  obs.peaks["bf16_flops_per_s"])

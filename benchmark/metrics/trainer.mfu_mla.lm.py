"""Model FLOP/s utilization of the third family's measured window, percent
of peaks.json's `bf16_flops_per_s`: the operations the window's steps
require (benchmark/lib/mlashapes.py: the latent projections, the mixers'
coefficient products, dense MLP, routers and shared experts, the
module's projection, both head passes, attention over the causal pairs at
192 + 128 lanes a held head, the experts' products for the assignments
the counter `LM_HELD_ASSIGNMENTS` saw; backward at twice the forward, the
recomputed layer not counted) over the window's seconds. An end-to-end
utilization, not a kernel's roofline share: idle time is in it."""

from benchmark.lib import lmshapes, mlashapes


def read(obs):
    counts = lmshapes.window_counts(obs.window,
                                    ("LM_STEP", "LM_HELD_ASSIGNMENTS"))
    if counts is None or "heads_held" not in obs.shapes:
        return None
    flops = mlashapes.step_flops(counts[0], counts[1], obs.shapes)
    return lmshapes.share_of_peak(flops, obs.window.seconds,
                                  obs.peaks["bf16_flops_per_s"])

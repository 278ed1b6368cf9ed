"""Model FLOP/s utilization of the measured window of a cell whose
attention runs over a learned selection, percent of peaks.json's
`bf16_flops_per_s`: the operations the window's steps require
(benchmark/lib/sparseshapes.py: the dense products, attention over the
SELECTED pairs, the indexer's scores of every causal pair and its
divergence, the head, the experts' products for the assignments the
counter `LM_HELD_ASSIGNMENTS` saw; backward at twice the forward, the
recomputed layer not counted) over the window's seconds. An end-to-end
utilization, not a kernel's roofline share: idle time is in it."""

from benchmark.lib import lmshapes, sparseshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window,
                                    ("LM_STEP", "LM_HELD_ASSIGNMENTS"))
    if counts is None or "index_topk" not in obs.shapes:
        return None
    flops = sparseshapes.step_flops(counts[0], counts[1], obs.shapes)
    return lmshapes.share_of_peak(flops, obs.window.seconds,
                                  obs.peaks["bf16_flops_per_s"])

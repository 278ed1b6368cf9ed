"""Model FLOP/s utilization of the measured window, percent of
peaks.json's `bf16_flops_per_s`: the operations the window's steps require
over the window's seconds. ONE reader for every family of language model:
the cell's driver names its family (`family` in `ctx.shapes`) and
benchmark/lib/families.py gives that family's counting module, whose
`step_flops` counts what the MODEL needs, once, from the sizes and from
what the step's counters (`COUNTERS`) saw: the dense products of every
token, the mixers' proper work over the unmasked or selected pairs or the
recurrence as the function defines it, the experts' products for the
assignments `LM_HELD_ASSIGNMENTS` saw; backward at twice the forward, the
recomputed layer not counted. An end-to-end utilization, not a kernel's
roofline share: idle time is in it. Until PR 67 each family had an entry
of its own (`trainer.mfu_blockdiff.lm`, `_mla`, `_mixed`, `_sparse`,
`_kda`, `_solar`, `_lfm2`, `_granite`): their histories continue here.
None for shapes of no family or a program without the counters."""

from benchmark.lib import families, lmshapes


def read(obs):
    family = families.counting(obs.shapes)
    if family is None:
        return None
    counts = lmshapes.window_counts(obs.window, family.COUNTERS)
    if counts is None:
        return None
    return lmshapes.share_of_peak(family.step_flops(*counts, obs.shapes),
                                  obs.window.seconds,
                                  obs.peaks["bf16_flops_per_s"])

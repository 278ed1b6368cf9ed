"""Percent of the window's mixers (a layer a sequence) that are gated short
convolutions and not attention: counter `LM_MIXERS_CONV` over `LM_MIXERS`
(counted on the host from the configuration's layout,
`PSLMTrainer._count_stats`), measured window. 75 where three layers of four
are convolutions; None where the program has no such counter."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window, ("LM_MIXERS_CONV",
                                                 "LM_MIXERS"))
    if counts is None:
        return None
    return 100.0 * counts[0] / counts[1]

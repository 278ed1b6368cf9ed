"""Percent of the window's trained positions that the multi-token module
predicted from: counter `LM_MTP_TOKENS` over `LM_TOKENS`, measured window.
100 while every step runs the module over every position; under 100 the
module skipped steps or positions. None where the program counts no module
(a rank that holds none, an older program)."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window, ("LM_MTP_TOKENS",
                                                 "LM_TOKENS"))
    return None if counts is None else 100.0 * counts[0] / counts[1]

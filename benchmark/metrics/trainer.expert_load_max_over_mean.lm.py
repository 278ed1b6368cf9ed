"""The fullest held expert's tokens over the mean held expert's, over
the window's layers and sequences: counters `LM_EXPERT_MAX_TOKENS` (the
largest group of each layer's each sequence, summed) and
`LM_HELD_ASSIGNMENTS` (all groups, summed) of the measured window. 1 is
an even load; the grouped products' tiles fill worse as it grows."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(
        obs.window, ("LM_EXPERT_MAX_TOKENS", "LM_HELD_ASSIGNMENTS"))
    if counts is None or "held" not in obs.shapes:
        return None
    return counts[0] * obs.shapes["held"] / counts[1]

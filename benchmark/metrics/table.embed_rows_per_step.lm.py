"""Distinct embedding rows a step names (what the rows form of Adam
reads and writes, three tables' worth): counter `LM_EMBED_ROWS` over
`LM_STEP` in the measured window."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window, ("LM_EMBED_ROWS", "LM_STEP"))
    return None if counts is None else counts[0] / counts[1]

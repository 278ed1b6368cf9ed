"""Percent of the clean tokens trained whose noised copy was masked, so
that they carry a loss: counter `LM_MASKED_TOKENS` (computed on the
device, read a step late) over `LM_TOKENS`, measured window. With t ~
U(0.001, 1] a block it is near one half; the loss and the head's
gradient rest on that many positions a step."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window,
                                    ("LM_MASKED_TOKENS", "LM_TOKENS"))
    return None if counts is None else 100.0 * counts[0] / counts[1]

"""Percent of the causal (query, key) pairs that the selection keeps:
counter `LM_SELECTED_PAIRS` over `LM_CAUSAL_PAIRS` (both computed on the
device from the selection itself, every layer, read a step late),
measured window. At 16,384 positions and 2,048 keys a query it is 23.4
whatever the indexer has learned: each query past the 2,048th keeps
exactly 2,048; another reading means the search is not exact."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(
        obs.window, ("LM_SELECTED_PAIRS", "LM_CAUSAL_PAIRS"))
    return None if counts is None else 100.0 * counts[0] / counts[1]

"""Percent of the 512 x 512 tiles under the diagonal that hold a selected
pair: counter `LM_SELECT_TILES_LIVE` over `LM_SELECT_TILES`, measured
window. The attention kernel visits the live tiles and no other, so this
is what a tile-skipping kernel can save: 100 under an untrained indexer,
whose choices are spread over every tile."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(
        obs.window, ("LM_SELECT_TILES_LIVE", "LM_SELECT_TILES"))
    return None if counts is None else 100.0 * counts[0] / counts[1]

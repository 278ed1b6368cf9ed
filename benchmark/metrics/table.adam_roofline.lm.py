"""Adam's share of the HBM-bandwidth bound, percent: 28 bytes a parameter
(read weights, both moments and the gradient, write the first three) over
every whole table each step and over the embedding rows the traced
window's steps named (`LM_EMBED_ROWS`), over the device time of the
update programs' scopes (table.adam_ms_per_step.lm). Bound by memory
bandwidth: a dozen operations an element."""

from benchmark.lib import lmshapes

SCOPES = lmshapes.UPDATE_SCOPES


def read(obs):
    took = lmshapes.scopes_seconds(obs, SCOPES)
    counts = took and lmshapes.window_counts(obs.traced,
                                             ("LM_STEP", "LM_EMBED_ROWS"))
    if not counts:
        return None
    return lmshapes.share_of_peak(
        lmshapes.adam_bytes(counts[0], counts[1], obs.shapes), took,
        obs.peaks["hbm_bytes_per_s"])

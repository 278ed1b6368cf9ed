"""Percent of the state-space layers' (chunk, head) pairs whose log decay
summed over the chunk is under `delta.DEEP` (-20): counter `LM_SSD_DEEP`
(computed on the device in the scan itself, read a step late) over
`LM_SSD_CHUNKS` (chunks walked, a layer a sequence) times the layer's
heads, measured window. It is where a chunked form that divided by a decay
would have overflowed, and it grows with the chunk; 0 is a count too (every
pair shallow), None where the program counts no chunk."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window, ("LM_SSD_CHUNKS",))
    if counts is None or not obs.shapes.get("ssd_heads"):
        return None
    deep = obs.window.counters.get("LM_SSD_DEEP", {}).get("count", 0)
    return 100.0 * deep / (counts[0] * obs.shapes["ssd_heads"])

"""Percent of the delta layers' (chunk, head, channel) triples whose log
decay summed over the chunk is under -20: counter `LM_KDA_DECAY_DEEP` over
`LM_KDA_DECAY_CHANNELS` (both computed on the device in the scan itself,
every delta layer, read a step late), measured window. It is where a
chunked form that divided by a decay would have overflowed; 0 is a count
too (every channel shallow), None where the program counts neither."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window, ("LM_KDA_DECAY_CHANNELS",))
    if counts is None:
        return None
    deep = obs.window.counters.get("LM_KDA_DECAY_DEEP", {}).get("count", 0)
    return 100.0 * deep / counts[0]

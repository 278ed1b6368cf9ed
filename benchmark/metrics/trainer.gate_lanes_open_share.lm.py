"""Percent of the per-lane attention gate's lanes whose gate is over a half:
counter `LM_GATE_LANES_OPEN` over `LM_GATE_LANES` (a position a held head a
lane of every gated layer; counted on the device, read a step late),
measured window. Near 50 at fresh weights; a wrong sign or a missing sigmoid
shows here first. None where the program has no such counter."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window, ("LM_GATE_LANES",))
    if counts is None:
        return None
    open_ = obs.window.counters.get("LM_GATE_LANES_OPEN", {}).get("count", 0)
    return 100.0 * open_ / counts[0]

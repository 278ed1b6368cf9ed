"""Percent of the attention's output that the per-head gate lets through:
counter `LM_GATE_OPEN` (each layer's gates summed over its heads, the
step's mean over tokens, in thousandths; computed on the device, read a
step late) over the window's steps and the model's gated heads. 50 at
fresh weights; a wrong sign or a missing sigmoid shows here first. None
where the program has no such counter."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window, ("LM_GATE_OPEN", "LM_STEP"))
    if counts is None or "gate_heads" not in obs.shapes:
        return None
    return 100.0 * counts[0] / (1e3 * counts[1] * obs.shapes["gate_heads"])

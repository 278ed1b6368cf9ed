"""The fullest router output's assignments over the mean output's, over
the window's sparse layers (the multi-token module's among them): counter
`LM_ROUTER_LOAD_MAX` (each sparse layer's fullest output over ALL its
outputs, held or not, a step, summed) over the mean a layer a step,
`tokens x top_k / outputs`. 1 is an even load: what the bias the server
keeps is there to reach."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window,
                                    ("LM_ROUTER_LOAD_MAX", "LM_STEP"))
    if counts is None or "top_k" not in obs.shapes:
        return None
    s = obs.shapes
    mean = s["sequences"] * s["seq_len"] * s["top_k"] / s["router_outputs"]
    return counts[0] / (counts[1] * s["layers"] * mean)

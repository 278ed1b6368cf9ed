"""Device milliseconds a step under the scope `mv.lm.experts` (norm, the
sort of the assignments by expert, the rows' gather, the three grouped
products and the weighted sum back to tokens, forward and backward),
busiest chip, traced window."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.experts",))

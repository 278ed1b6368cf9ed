"""Device milliseconds a step in the state-space layers' short convolution
(the taps over x, B and C, the bias, silu), forward, made again and
backward: scope `mv.lm.attn.ssd.conv`, busiest chip, traced window. None
where the program has no such scope."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.attn.ssd.conv",))

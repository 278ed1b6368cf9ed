"""Device milliseconds a step in the delta layers' short convolutions (q,
k and v through a causal depthwise convolution over positions and silu),
forward and backward: scope `mv.lm.attn.kda.conv`, busiest chip, traced
window. None where the program has no such scope."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.attn.kda.conv",))

"""Device milliseconds a step in the attention's per-head output gate
(the gate's product `h W_g`, its sigmoid and the multiply with the heads'
outputs, forward and backward): scope `mv.lm.attn.gate`, busiest chip,
traced window. None where the program has no such scope."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.attn.gate",))

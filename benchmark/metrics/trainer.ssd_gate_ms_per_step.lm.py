"""Device milliseconds a step in the state-space layers' gate and gated
norm (`Y silu(z)` normed a head group), forward, made again and backward:
scope `mv.lm.attn.ssd.gate`, busiest chip, traced window. None where the
program has no such scope."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.attn.ssd.gate",))

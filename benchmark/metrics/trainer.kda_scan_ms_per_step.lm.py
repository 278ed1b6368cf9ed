"""Device milliseconds a step in the delta rule's scan (the chunks' own
parts, the solve, the state from chunk to chunk), forward, made again and
backward: scope `mv.lm.attn.kda.scan`, busiest chip, traced window. None
where the program has no such scope."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.attn.kda.scan",))

"""Device milliseconds a step in the convolution layers' mixers (norm,
`W_in`, both gates, the taps, `W_out`), forward and backward: scopes
`mv.lm.attn.shortconv` and `mv.lm.attn.shortconv.taps`, busiest chip,
traced window. None where the program has no such scope."""

from benchmark.lib import lmshapes

SCOPES = ("mv.lm.attn.shortconv", "mv.lm.attn.shortconv.taps")


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, SCOPES)

"""Device milliseconds a step in the sliding-window layers' attention
blocks (norm, projections, rotary positions, the attention proper, output
projection), forward and backward: scopes `mv.lm.attn.window` and
`mv.lm.attn.window.kernel`, busiest chip, traced window."""

from benchmark.lib import lmshapes

SCOPES = ("mv.lm.attn.window", "mv.lm.attn.window.kernel")


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, SCOPES)

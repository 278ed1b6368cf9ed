"""Device milliseconds a step in the full-attention layers' attention
blocks (norm, projections, the attention proper, output projection),
forward and backward: scopes `mv.lm.attn.full` and
`mv.lm.attn.full.kernel`, busiest chip, traced window."""

from benchmark.lib import lmshapes

SCOPES = ("mv.lm.attn.full", "mv.lm.attn.full.kernel")


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, SCOPES)

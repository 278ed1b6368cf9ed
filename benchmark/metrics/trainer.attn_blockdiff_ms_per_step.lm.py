"""Device milliseconds a step in the block-diffusion layers' attention
blocks (norm, projections, the heads' q and k norms, rotary, the
attention proper, output projection), forward and backward: scopes
`mv.lm.attn.blockdiff` and `mv.lm.attn.blockdiff.kernel`, busiest chip,
traced window."""

from benchmark.lib import lmshapes

SCOPES = ("mv.lm.attn.blockdiff", "mv.lm.attn.blockdiff.kernel")


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, SCOPES)

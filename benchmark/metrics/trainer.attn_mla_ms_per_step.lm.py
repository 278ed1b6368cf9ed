"""Device milliseconds a step under the latent attention's scopes:
`mv.lm.attn.mla` (the sublayer's norm, both low-rank projections with
their norms, rotary positions, the output projection) and
`mv.lm.attn.mla.kernel` (the attention proper), forward and backward,
every layer and the multi-token module's; busiest chip, traced window."""

from benchmark.lib import lmshapes

SCOPES = ("mv.lm.attn.mla", "mv.lm.attn.mla.kernel")


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, SCOPES)

"""Device milliseconds a step under the delta layers' own scope,
`mv.lm.attn.kda`: the sublayer's norm, the nine projections, the L2 norms,
the log decay, beta, the gated output norm and the output projection,
forward and backward, every delta layer; the convolutions and the scan are
under scopes of their own (`trainer.kda_conv_ms_per_step.lm`,
`trainer.kda_scan_ms_per_step.lm`). Busiest chip, traced window. None
where the program has no such scope."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.attn.kda",))

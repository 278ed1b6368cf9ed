"""Device milliseconds a step under the scope `mv.lm.hc`: the residual
streams' coefficients (a norm over all streams, the product with `phi`,
Sinkhorn's rounds) and the two mixes of every sublayer, forward and
backward; busiest chip, traced window."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.hc",))

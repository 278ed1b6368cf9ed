"""Device milliseconds a block under the scope `mv.sgns.step` (the PS
trainer's step program: logits, gradients, the rows' deltas), busiest
chip, traced window."""

from benchmark.lib import xplane

SCOPE = "mv.sgns.step"


def read(obs):
    return xplane.scope_ms_per_round(obs, SCOPE)

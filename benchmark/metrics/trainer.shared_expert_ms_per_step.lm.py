"""Device milliseconds a step in the feed-forwards every token goes
through: the shared expert of every sparse layer (`mv.lm.shared_expert`)
and the dense layers' MLP (`mv.lm.dense_mlp`), forward and backward;
busiest chip, traced window."""

from benchmark.lib import lmshapes

SCOPES = ("mv.lm.shared_expert", "mv.lm.dense_mlp")


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, SCOPES)

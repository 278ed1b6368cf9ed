"""Device milliseconds a step under the scope `mv.lm.select`: each
query's exact top-k among the keys before it (the search for its
threshold, on a TPU in one kernel with the index scores it reads), the
selection's tiles and their counts, in the forward program and again in
the backward program's recomputation, busiest chip, traced window."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.select",))

"""Device milliseconds a step in the indexer outside the search: its
projections, the index key's LayerNorm, the rotary turn, the index scores
where they run under their own scope, and the divergence with its
gradients: scopes `mv.lm.indexer` and `mv.lm.indexer.loss`, forward and
backward, busiest chip, traced window. On a TPU the scores that the
search reads are computed inside its kernel and are under
`trainer.select_ms_per_step.lm`."""

from benchmark.lib import lmshapes

SCOPES = ("mv.lm.indexer", "mv.lm.indexer.loss")


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, SCOPES)

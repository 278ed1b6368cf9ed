"""Device milliseconds a step under the scope `mv.lm.head` (final norm,
logits over the vocabulary slice a block of tokens at a time, the loss
and its gradients), busiest chip, traced window."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.head",))

"""Device milliseconds a step under the scope `mv.lm.mtp.head`: the
multi-token module's pass of the model's OWN head (the module's final norm,
logits over the vocabulary slice a block of tokens at a time, the second
loss and its gradients), busiest chip, traced window. Beside
`trainer.head_ms_per_step.lm` it says what the second objective costs the
head."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.mtp.head",))

"""Device milliseconds a step under the scope `mv.lm.router` (the
router's float32 product, softmax and top-k, forward and backward),
busiest chip, traced window."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.router",))

"""Device milliseconds a block under the scope `mv.update.scatter_add`
(the row scatter-add of a block's two Adds, or of the local trainer's
group program), busiest chip, traced window, every program summed. The
profiler times every row DMA of the kernel, so this reads longer than an
untraced block's share."""

from benchmark.lib import xplane

SCOPE = "mv.update.scatter_add"


def read(obs):
    return xplane.scope_ms_per_round(obs, SCOPE)

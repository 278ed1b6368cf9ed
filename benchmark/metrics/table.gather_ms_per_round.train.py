"""Device milliseconds a block under the scope `mv.table.gather` (the row
gather of a block's two Gets; on a row-sharded table its all-reduce
too), busiest chip, traced window, every program summed."""

from benchmark.lib import xplane

SCOPE = "mv.table.gather"


def read(obs):
    return xplane.scope_ms_per_round(obs, SCOPE)

"""Percent of a host row-id Add's shards that the worker cut as views of
the request: Dashboard ADD_ROWS_SHARD_VIEW over ADD_ROWS_SHARD_VIEW +
ADD_ROWS_SHARD_COPIED (one a shard `MatrixWorker.partition` made: a run
of the request's keys and values, or a masked gather into a fresh array),
measured window. Under 100 a host Add has left the form its time was
measured on."""

from benchmark.lib import counters


def read(obs):
    return counters.share(obs.window.counters, "ADD_ROWS_SHARD_VIEW",
                          "ADD_ROWS_SHARD_COPIED")

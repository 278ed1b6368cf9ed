"""Percent of a Get's reply shards that the worker copied straight into
the caller's buffer: Dashboard GET_REPLY_ROWS_DIRECT over
GET_REPLY_ROWS_DIRECT + GET_REPLY_ROWS_PLACED (one a shard `place_rows`
placed: the shard is the request or a run of it, or it was searched for
row by row), measured window. Under 100 a host Get has left the form its
latency was measured on."""

from benchmark.lib import counters


def read(obs):
    return counters.share(obs.window.counters, "GET_REPLY_ROWS_DIRECT",
                          "GET_REPLY_ROWS_PLACED")

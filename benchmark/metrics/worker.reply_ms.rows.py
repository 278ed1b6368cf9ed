"""Milliseconds of the worker actor's reply handling a Get (Dashboard
WORKER_REPLY_GET over its count, measured window, profiler off): the
device-to-host copy, the reshape and the placement into the caller's
buffer, on the worker's thread."""

from benchmark.lib import counters


MONITORS = ('WORKER_REPLY_GET',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

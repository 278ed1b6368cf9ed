"""Milliseconds the worker actor spent on one Add's acknowledgement
(Dashboard WORKER_REPLY_ADD over its count: the version stamp, the
waiter's notify and its completion callbacks; measured window, profiler
off)."""

from benchmark.lib import counters


MONITORS = ('WORKER_REPLY_ADD',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

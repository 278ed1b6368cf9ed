"""Milliseconds the calling thread spent blocked in WorkerTable.wait, over
the waits it made (Dashboard TABLE_WAIT, measured window, profiler off):
one wait a get_rows or add_rows. What is left of a request's caller-side
time is issuing it."""

from benchmark.lib import counters


MONITORS = ('TABLE_WAIT',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

"""Milliseconds of one host pad of an Add's delta (Dashboard UPDATE_PAD_ROWS
over its count: `updater/engine.py` `pad_rows`, `np.pad` into a fresh
bucket-sized array; measured window, profiler off). One half of the
server's Add handler; a device delta does not enter it."""

from benchmark.lib import counters


MONITORS = ('UPDATE_PAD_ROWS',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

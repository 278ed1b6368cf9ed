"""Milliseconds a beat of the program's heartbeat thread ran after its
10 ms sleep was due (Dashboard HOST_BEAT_LATE over its count, measured
window, profiler off): how long a thread that wants to run waits for the
interpreter's lock or a core. Nothing where no beat was counted."""

from benchmark.lib import counters


MONITORS = ('HOST_BEAT_LATE',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

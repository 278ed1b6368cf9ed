"""Milliseconds one reply's copy off the device WAITED for the program that
makes the array (Dashboard BLOB_D2H_READY over its count: `block_until_ready`
inside `BLOB_D2H`; measured window, profiler off). In a closed loop the
gather waits for the Add before it: that wait is here."""

from benchmark.lib import counters


MONITORS = ('BLOB_D2H_READY',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

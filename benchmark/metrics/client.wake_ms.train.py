"""Milliseconds from the worker actor's completing notify to the trainer's
thread running again in WorkerTable.wait (Dashboard TABLE_WAKE over its
count: only waits that blocked; measured window, profiler off). The tail
of `client.wait_ms.train`: two threads, one GIL."""

from benchmark.lib import counters


MONITORS = ('TABLE_WAKE',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

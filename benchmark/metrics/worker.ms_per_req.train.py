"""Host milliseconds inside the worker actor's Get and Add handlers over the
requests they counted (Dashboard WORKER_PROCESS_GET + WORKER_PROCESS_ADD),
measured window only. They time the enqueue, not the device."""

from benchmark.lib import counters


MONITORS = ('WORKER_PROCESS_GET', 'WORKER_PROCESS_ADD')


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

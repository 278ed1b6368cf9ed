"""Host milliseconds inside the server actor's Get and Add handlers over the
requests they counted (Dashboard SERVER_PROCESS_GET + SERVER_PROCESS_ADD),
measured window only. They time the enqueue, not the device."""

from benchmark.lib import counters


MONITORS = ('SERVER_PROCESS_GET', 'SERVER_PROCESS_ADD')


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

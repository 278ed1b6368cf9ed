"""Milliseconds of one update program's jitted call (Dashboard
UPDATE_DISPATCH over its count; measured window, profiler off): for a
host delta the upload is inside it. The other half of the server's Add
handler."""

from benchmark.lib import counters


MONITORS = ('UPDATE_DISPATCH',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

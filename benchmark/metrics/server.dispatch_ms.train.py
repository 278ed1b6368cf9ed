"""Milliseconds of one jitted call inside the server's handlers (Dashboard
UPDATE_DISPATCH + TABLE_GATHER_DISPATCH over their counts: an Add's update
program, a row Get's pad_ids and gather; measured window, profiler off).
Against `server.ms_per_req.train` it says whether the dispatch or the rest
of the handler is the handler's time."""

from benchmark.lib import counters


MONITORS = ('UPDATE_DISPATCH', 'TABLE_GATHER_DISPATCH')


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

"""Milliseconds the caller's thread spent issuing one Add (Dashboard
CLIENT_ISSUE_ADD over its count: id checks, the delta made contiguous, the
client cache's begin_add, blobs, the send; measured window, profiler
off)."""

from benchmark.lib import counters


MONITORS = ('CLIENT_ISSUE_ADD',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

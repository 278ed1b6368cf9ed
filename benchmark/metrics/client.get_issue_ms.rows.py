"""Milliseconds the caller's thread spent issuing one Get (Dashboard
CLIENT_ISSUE_GET over its count: the public async entry, id checks and
blobs to the message in the worker actor's mailbox; measured window,
profiler off)."""

from benchmark.lib import counters


MONITORS = ('CLIENT_ISSUE_GET',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

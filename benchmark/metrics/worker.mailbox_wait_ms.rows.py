"""Milliseconds a message (a request from the caller, a reply from the
server) sat in the worker actor's mailbox, receive to pop (Dashboard
MAILBOX_WAIT[worker] over its count, measured window, profiler off): the
`.train` reader's twin for the rows cells."""

from benchmark.lib import counters


MONITORS = ('MAILBOX_WAIT[worker]',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

"""Milliseconds a message sat in the server actor's mailbox, receive to
pop (Dashboard MAILBOX_WAIT[server] over its count, measured window,
profiler off). One closed-loop client: the server is idle when a request
arrives, so this is the hand-over between threads."""

from benchmark.lib import counters


MONITORS = ('MAILBOX_WAIT[server]',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

"""Milliseconds a message sat in the server actor's mailbox, receive to
pop (Dashboard MAILBOX_WAIT[server] over its count, measured window,
profiler off): a block's Gets queue here behind the previous block's two
fire-and-forget Adds."""

from benchmark.lib import counters


MONITORS = ('MAILBOX_WAIT[server]',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

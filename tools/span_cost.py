#!/usr/bin/env python3
"""What the always-on instrumentation costs with no profiler session
open, in host microseconds a block of the PS trainer (2 Gets + 2 Adds
through both actors):

    python3 tools/span_cost.py

A block enters 8 handler monitors with a request's id (WORKER_PROCESS_
GET/ADD, SERVER_PROCESS_GET/ADD, two each), 2 WORKER_REPLY_GET, 2
TABLE_WAIT, and stamps and closes 12 MAILBOX_WAIT (8 messages through
the worker's mailbox, 4 through the server's). Run from a checkout of
the commit before PR 24 it times what that tree has: the 8 handler
monitors, without arguments. The difference of the two sums is what
PR 24 added to a block, summed over the three threads.
"""

import os
import sys
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from multiverso_tpu.core.message import Message  # noqa: E402
from multiverso_tpu.runtime.actor import Actor  # noqa: E402
from multiverso_tpu.util.dashboard import monitor  # noqa: E402

N = 200_000


class _Zoo:
    rank = 0

    def register_actor(self, actor):
        pass


def us(fn) -> float:
    fn()
    return min(timeit.repeat(fn, number=N, repeat=7)) / N * 1e6


def plain():
    with monitor("TABLE_WAIT"):
        pass


def main() -> None:
    costs = {"monitor": us(plain)}
    block = 8 * costs["monitor"]
    if hasattr(Actor, "_popped"):
        msg = Message(msg_id=7, table_id=1)

        def with_args():
            with monitor("WORKER_REPLY_GET", msg_id=msg.msg_id,
                         table=msg.table_id):
                pass

        actor = Actor("span_cost", _Zoo())

        def mailbox():
            actor.receive(msg)
            actor._popped(actor.mailbox.pop())

        def queue_only():
            actor.mailbox.push(msg)
            actor.mailbox.pop()

        costs["monitor_with_request_id"] = us(with_args)
        costs["mailbox_wait"] = us(mailbox) - us(queue_only)
        block = (2 * costs["monitor"]
                 + 10 * costs["monitor_with_request_id"]
                 + 12 * costs["mailbox_wait"])
    for name, cost in costs.items():
        print(f"{name}: {cost:.3f} us")
    print(f"sites of one block: {block:.2f} us")


if __name__ == "__main__":
    main()

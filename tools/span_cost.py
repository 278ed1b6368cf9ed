#!/usr/bin/env python3
"""What the always-on instrumentation costs with no profiler session
open, in host microseconds a block of the PS trainer (2 Gets + 2 Adds
through both actors):

    python3 tools/span_cost.py

A block enters 18 monitors with a request's id or table (WORKER_PROCESS_
GET/ADD and SERVER_PROCESS_GET/ADD two each, 2 WORKER_REPLY_GET, 2
WORKER_REPLY_ADD, 2 TABLE_WAIT, 4 CLIENT_ISSUE_GET/ADD), 7 without
(the trainer's TRAINER_BLOCK_IDS/STEP/PACE, 2 UPDATE_DISPATCH and 2
TABLE_GATHER_DISPATCH inside the server's handlers; 8 before PR 43, when
the loop entered TRAINER_BLOCK_UPLOAD/IDS/STEP/LOSS), stamps and closes
12 MAILBOX_WAIT (8 messages through the worker's mailbox, 4 through the
server's) and adds 2 TABLE_WAKE (a stamp and a Monitor.add, as a
mailbox's). Since PR 52 every entry's way into its Monitor compares its
length with the floor over which the heartbeat is told of it
(`dashboard.LONG_ENTRY_MS`): `monitor_add` is a `Monitor.add` under the
floor, `monitor_add_long` one over it (a tuple appended to a deque, which
only an entry of 40 ms pays). Run from a checkout of an older commit it
times the sites that tree has: before PR 37 10 with an id, 2 without, 12
MAILBOX_WAIT; before PR 24 the 8 handler monitors, without arguments.

Since PR 68 it also times what the listeners to program builds
(`dashboard.listen_to_program_builds`) cost an EVENT of `jax.monitoring`,
a stage's begin and end together, less what JAX's own empty call costs:
`stage_nested` is a trace event inside an open trace (a depth bump and a
return, twice: tracing a program emits one for every jitted function
traced inside it, tens of thousands a process), `stage_outermost` one
that is counted (a `Monitor.add` and the program's row). Both run in
set-up alone: nothing is built inside a steady loop.
"""

import os
import sys
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from multiverso_tpu.core.message import Message  # noqa: E402
from multiverso_tpu.runtime.actor import Actor  # noqa: E402
from multiverso_tpu.util import dashboard  # noqa: E402
from multiverso_tpu.util.dashboard import monitor  # noqa: E402

N = 200_000


class _Zoo:
    rank = 0

    def register_actor(self, actor):
        pass


def us(fn) -> float:
    fn()
    return min(timeit.repeat(fn, number=N, repeat=7)) / N * 1e6


def _has_caller_spans() -> bool:
    from multiverso_tpu.util.dashboard import METRIC_NAMES
    return "CLIENT_ISSUE_GET" in METRIC_NAMES


def _plain_monitors() -> int:
    """Monitors without arguments a block enters, in the tree this runs
    from."""
    return 7 if _has_caller_spans() else 2


def plain():
    with monitor("TABLE_WAIT"):
        pass


_TRACE = "/jax/core/compile/jaxpr_trace_duration"


def _stage(name: str):
    import jax.monitoring

    def event():
        jax.monitoring.record_scalar(_TRACE, 0.0, fun_name=name)
        jax.monitoring.record_event_duration_secs(_TRACE, 1e-4,
                                                  fun_name=name)
    return event


def stage_costs() -> dict:
    """Microseconds a stage event (begin and end) with the listeners on,
    less the same calls with nobody listening; {} in a tree without
    them."""
    if not hasattr(dashboard, "listen_to_program_builds"):
        return {}
    nested, outermost = _stage("sin"), _stage("backward")
    bare = us(nested)
    dashboard.listen_to_program_builds()
    costs = {"stage_outermost": us(outermost) - bare}
    dashboard._stage_begins(_TRACE, 0.0, fun_name="outer")   # held open
    costs["stage_nested"] = us(nested) - bare
    dashboard._stage_ends(_TRACE, 0.0, fun_name="outer")
    dashboard.stop_listening_to_program_builds()
    return costs


def main() -> None:
    costs = {"monitor": us(plain)}
    counted = dashboard.Dashboard.get("TABLE_WAKE")
    floor = getattr(dashboard, "LONG_ENTRY_MS", None)
    costs["monitor_add"] = us(lambda: counted.add(0.02))
    if floor is not None:
        costs["monitor_add_long"] = us(lambda: counted.add(floor + 1.0))
        dashboard.long_entries.clear()
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
        issued = _has_caller_spans()
        block = (_plain_monitors() * costs["monitor"]
                 + (18 if issued else 10) * costs["monitor_with_request_id"]
                 + (14 if issued else 12) * costs["mailbox_wait"])
    costs.update(stage_costs())
    for name, cost in costs.items():
        print(f"{name}: {cost:.3f} us")
    print(f"sites of one block: {block:.2f} us")


if __name__ == "__main__":
    main()

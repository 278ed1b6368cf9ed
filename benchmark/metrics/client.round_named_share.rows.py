"""Percent of the measured window that lies under a monitor of the request
path: CLIENT_ISSUE_GET/ADD, MAILBOX_WAIT[worker], MAILBOX_WAIT[server],
WORKER_PROCESS_GET/ADD, SERVER_PROCESS_GET/ADD, WORKER_REPLY_GET/ADD and
TABLE_WAKE, none nested in another of them (Dashboard milliseconds over
the window's; profiler off). The cell is one strictly serial closed loop,
so what is left of 100 is what has no name yet. None where the program
has no monitor on the caller's thread."""

MONITORS = (
    "CLIENT_ISSUE_GET", "CLIENT_ISSUE_ADD", "MAILBOX_WAIT[worker]",
    "MAILBOX_WAIT[server]", "WORKER_PROCESS_GET", "WORKER_PROCESS_ADD",
    "SERVER_PROCESS_GET", "SERVER_PROCESS_ADD", "WORKER_REPLY_GET",
    "WORKER_REPLY_ADD", "TABLE_WAKE")


def read(obs):
    counters = obs.window.counters
    if not counters.get("CLIENT_ISSUE_GET", {}).get("count") \
            or not obs.window.seconds:
        return None
    named = sum(counters.get(name, {}).get("ms", 0.0) for name in MONITORS)
    return 100.0 * named / (obs.window.seconds * 1e3)

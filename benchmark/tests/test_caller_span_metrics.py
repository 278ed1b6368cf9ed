"""The fifteen readers PR 37 entered: the monitors on the caller's thread
(CLIENT_ISSUE_*, TABLE_WAKE, TRAINER_*), the ack's way back
(WORKER_REPLY_ADD), the halves of the two wide spans (BLOB_D2H_READY/COPY,
UPDATE_PAD_ROWS/DISPATCH, TABLE_GATHER_DISPATCH) and PR 36's pair of
counters, each on a hand-built ``Observations``: a number from counts and
milliseconds, nothing where its monitor counted nothing, and nothing,
without an exception, from a program that has no such monitor (the parent
commit, which the driver runs them on too)."""

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

COUNTERS = {
    "CLIENT_ISSUE_GET": {"count": 300, "ms": 600.0},
    "CLIENT_ISSUE_ADD": {"count": 300, "ms": 1500.0},
    "TABLE_WAKE": {"count": 600, "ms": 30.0},
    "WORKER_REPLY_ADD": {"count": 300, "ms": 15.0},
    "WORKER_REPLY_GET": {"count": 300, "ms": 6900.0},
    "WORKER_PROCESS_GET": {"count": 300, "ms": 45.0},
    "WORKER_PROCESS_ADD": {"count": 300, "ms": 60.0},
    "SERVER_PROCESS_GET": {"count": 300, "ms": 900.0},
    "SERVER_PROCESS_ADD": {"count": 300, "ms": 9450.0},
    "MAILBOX_WAIT[worker]": {"count": 1200, "ms": 120.0},
    "MAILBOX_WAIT[server]": {"count": 600, "ms": 60.0},
    "UPDATE_PAD_ROWS": {"count": 300, "ms": 6000.0},
    "UPDATE_DISPATCH": {"count": 300, "ms": 3000.0},
    "TABLE_GATHER_DISPATCH": {"count": 300, "ms": 600.0},
    "BLOB_D2H_READY": {"count": 300, "ms": 3900.0},
    "BLOB_D2H_COPY": {"count": 300, "ms": 1500.0},
    "TRAINER_BLOCK_UPLOAD": {"count": 300, "ms": 150.0},
    "TRAINER_BLOCK_IDS": {"count": 300, "ms": 90.0},
    "TRAINER_BLOCK_STEP": {"count": 300, "ms": 120.0},
    "TRAINER_BLOCK_LOSS": {"count": 300, "ms": 60.0},
    "ADD_ROWS_SHARD_VIEW": {"count": 297, "ms": 0.0},
    "ADD_ROWS_SHARD_COPIED": {"count": 3, "ms": 0.0},
}
ROUNDS, SECONDS = 300, 20.0

WANT = {
    "trainer.dispatch_ms_per_round.train": 1.4,
    "client.issue_ms_per_round.train": 7.0,
    "client.wake_ms.train": 0.05,
    "server.dispatch_ms.train": 6.0,
    "client.get_issue_ms.rows": 2.0,
    "client.add_issue_ms.rows": 5.0,
    "client.wake_ms.rows": 0.05,
    "worker.reply_add_ms.rows": 0.05,
    "worker.mailbox_wait_ms.rows": 0.1,
    "server.add_pad_ms.rows": 20.0,
    "server.add_dispatch_ms.rows": 10.0,
    "client.d2h_wait_ms.rows": 13.0,
    "client.d2h_copy_ms.rows": 5.0,
    # 600 + 1500 + 120 + 60 + 45 + 60 + 900 + 9450 + 6900 + 15 + 30 ms
    "client.round_named_share.rows": 100.0 * 19680.0 / 20000.0,
    "client.add_view_share.rows": 99.0,
}


class _Window:
    def __init__(self, counters, rounds):
        self.counters, self.rounds, self.seconds = counters, rounds, SECONDS


def _read(name, counters, rounds=ROUNDS):
    return load_module("metrics", name).read(
        Observations(window=_Window(counters, rounds)))


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(name):
    assert _read(name, COUNTERS) == pytest.approx(WANT[name])
    # counted nothing in the window: no number, not a zero
    zero = {k: {"count": 0, "ms": 0.0} for k in COUNTERS}
    assert _read(name, zero) is None
    # the parent's monitors alone: no number, and no exception (the one
    # reader of a monitor the parent has, the mailbox's, set apart)
    parent = {k: COUNTERS[k] for k in (
        "WORKER_REPLY_GET", "WORKER_PROCESS_GET", "WORKER_PROCESS_ADD",
        "SERVER_PROCESS_GET", "SERVER_PROCESS_ADD", "MAILBOX_WAIT[server]")}
    assert _read(name, parent) is None


def test_the_local_trainer_s_group_dispatch_is_read_a_block():
    """One TRAINER_GROUP_DISPATCH a group of eight blocks, over the
    window's blocks; no round, no number."""
    counters = {"TRAINER_GROUP_DISPATCH": {"count": 25, "ms": 50.0}}
    name = "trainer.dispatch_ms_per_round.train"
    assert _read(name, counters, rounds=200) == pytest.approx(0.25)
    assert _read(name, counters, rounds=0) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_is_an_entry_found_by_name_with_its_cells(name, root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", name)
    entries.check_entry(root, bench, "per_layer", metric)
    assert metric["source"] == "program_span"
    cells = {w["name"] for w in bench["workloads"]}
    assert metric["workloads"] and set(metric["workloads"]) <= cells
    suffix = name.rsplit(".", 1)[1]
    for cell in metric["workloads"]:
        assert cell.startswith("mperf16m.") == (suffix == "rows")

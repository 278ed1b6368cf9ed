"""The ten readers of the program's own monitors (PR 24), each on a
hand-built ``Observations``: counts and milliseconds in, the value out;
nothing where the count is 0, and nothing where the program has no such
monitor (the parent commit, which the driver also runs them on). And
what every entry of ``BENCHMARK.json`` has to have, wherever it stands:
every list grows at its end, so no test may count from there, and each
of these runs on the repo and on the copy a later PR appended to."""

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries
from benchmark.tests.later_pr import ROOTS, bench_at

COUNTERS = {
    "TABLE_WAIT": {"count": 250, "ms": 19000.0},
    "WORKER_REPLY_GET": {"count": 125, "ms": 10000.0},
    "BLOB_D2H": {"count": 125, "ms": 2500.0},
    "BLOB_D2H_BYTES": {"count": 125 * 20_000_000, "ms": 0.0},
    "CLIENT_PLACE_ROWS": {"count": 250, "ms": 5000.0},   # two shards a Get
    "MAILBOX_WAIT[server]": {"count": 500, "ms": 100.0},
    "MAILBOX_WAIT[worker]": {"count": 1000, "ms": 50.0},
    "TRAINER_EPOCH_PREP": {"count": 2, "ms": 9700.0},
}
ROUNDS = 2000

WANT = {
    "client.wait_ms.train": 19000.0 / ROUNDS,
    "client.wait_ms.rows": 19000.0 / 250,
    "worker.reply_ms.rows": 80.0,
    "client.d2h_ms.rows": 20.0,
    "client.d2h_gb_per_s.rows": 1.0,
    "client.place_ms.rows": 40.0,
    "server.mailbox_wait_ms.train": 0.2,
    "server.mailbox_wait_ms.rows": 0.2,
    "worker.mailbox_wait_ms.train": 0.05,
    "trainer.prep_s.train": 4.85,
}


class _Window:
    def __init__(self, counters, rounds):
        self.counters, self.rounds = counters, rounds


def _read(name, counters, rounds=ROUNDS):
    return load_module("metrics", name).read(
        Observations(window=_Window(counters, rounds)))


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(name):
    assert _read(name, COUNTERS) == pytest.approx(WANT[name])
    # counted nothing in the window: no number, not a zero
    zero = {k: {"count": 0, "ms": 0.0} for k in COUNTERS}
    assert _read(name, zero) is None
    # a program without these monitors: no number, and no exception
    assert _read(name, {"SERVER_PROCESS_GET": {"count": 9, "ms": 1.0}}) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_ten_are_entries_of_the_benchmark(name, root):
    """Found by name: a later PR appends its entries after them."""
    entries.check_the_ten(entries.bench_of(root), [name])


EVERY_ENTRY = [(which, kind, metric["name"]) for which in ROOTS
               for kind, metric in entries.entries(bench_at(which))]


@pytest.mark.parametrize("which, kind, name", EVERY_ENTRY,
                         ids=[":".join(e) for e in EVERY_ENTRY])
def test_every_entry_has_a_reader_a_source_and_cells(
        which, kind, name, root_of):
    root = root_of(which)
    bench = entries.bench_of(root)
    entries.check_entry(root, bench, kind, entries.named(bench, kind, name))


def test_no_name_twice_and_no_retired_metric(root):
    bench, without_entry = entries.check_all(root)
    names = {m["name"] for _, m in entries.entries(bench)}
    assert "trainer.epoch_start_s.train" not in names | without_entry
    # every reader on disk has its entry (PR 27's waited four PRs for one)
    assert without_entry == set()

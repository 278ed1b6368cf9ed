"""The ten readers of the program's own monitors (PR 24), each on a
hand-built ``Observations``: counts and milliseconds in, the value out;
nothing where the count is 0, and nothing where the program has no such
monitor (the parent commit, which the driver also runs them on). And
what every entry of ``BENCHMARK.json`` has to have, wherever it stands:
``per_layer`` grows at its end, so no test may count from there."""

import os

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = entries.bench_of(ROOT)

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
def test_the_ten_are_entries_of_the_benchmark(name):
    """Found by name: a later PR appends its entries after them."""
    entries.check_the_ten(BENCH, [name])


@pytest.mark.parametrize(
    "kind, metric", entries.entries(BENCH),
    ids=[f"{kind}:{m['name']}" for kind, m in entries.entries(BENCH)])
def test_every_entry_has_a_reader_a_source_and_cells(kind, metric):
    entries.check_entry(ROOT, BENCH, kind, metric)


def test_no_name_twice_and_no_retired_metric():
    bench, without_entry = entries.check_all(ROOT)
    names = {m["name"] for _, m in entries.entries(bench)}
    assert "trainer.epoch_start_s.train" not in names | without_entry
    # every reader on disk has its entry (PR 27's waited four PRs for one)
    assert without_entry == set()

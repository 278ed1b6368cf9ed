"""The ten readers of the program's own monitors (PR 24), each on a
hand-built ``Observations``: counts and milliseconds in, the value out;
nothing where the count is 0, and nothing where the program has no such
monitor (the parent commit, which the driver also runs them on)."""

import json
import os

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

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


def test_the_ten_are_the_last_entries_of_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    added = bench["per_layer"][-len(WANT):]
    assert {m["name"] for m in added} == set(WANT)
    # each in one cell, under a layer and an end-to-end metric that exist
    layers = {m["layer"] for m in bench["per_layer"][:-len(WANT)]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in added:
        assert m["source"] == "program_span"
        assert m["layer"] in layers and set(m["workloads"]) <= cells
        moved, = [e for e in bench["end_to_end"] if e["name"] == m["moves"]]
        assert set(m["workloads"]) <= set(moved["workloads"])

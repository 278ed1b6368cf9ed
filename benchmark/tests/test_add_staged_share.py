"""The reader PR 38 entered, `server.add_staged_share.rows`: PR 38's pair
of counters (UPDATE_PAD_STAGED, UPDATE_PAD_FRESH: one a host delta that
`updater/engine.py` `pad_rows` padded) on a hand-built ``Observations``:
a percentage from the counts, nothing where neither counted, and nothing,
without an exception, from a program that has no such counter (the parent
commit, which the driver runs it on too)."""

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

NAME = "server.add_staged_share.rows"

COUNTERS = {
    "UPDATE_PAD_STAGED": {"count": 297, "ms": 0.0},
    "UPDATE_PAD_FRESH": {"count": 3, "ms": 0.0},
    "UPDATE_PAD_ROWS": {"count": 300, "ms": 600.0},
    "UPDATE_DISPATCH": {"count": 300, "ms": 1500.0},
}
#: what the parent's program counts on an Add's way through the server
PARENT = ("UPDATE_PAD_ROWS", "UPDATE_DISPATCH")


class _Window:
    def __init__(self, counters):
        self.counters, self.rounds, self.seconds = counters, 300, 20.0


def _read(counters):
    return load_module("metrics", NAME).read(
        Observations(window=_Window(counters)))


@pytest.mark.parametrize("staged, fresh, want", [
    (297, 3, 99.0), (300, 0, 100.0), (0, 300, 0.0), (0, 0, None)])
def test_reader(staged, fresh, want):
    counters = dict(COUNTERS,
                    UPDATE_PAD_STAGED={"count": staged, "ms": 0.0},
                    UPDATE_PAD_FRESH={"count": fresh, "ms": 0.0})
    got = _read(counters)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_parent_s_monitors_alone_give_nothing():
    assert _read({k: COUNTERS[k] for k in PARENT}) is None
    assert _read({}) is None


def test_one_counter_alone_is_a_share_too():
    """A window in which every delta was staged has no UPDATE_PAD_FRESH
    entry at all (a counter exists from its first count)."""
    assert _read({"UPDATE_PAD_STAGED": {"count": 5, "ms": 0.0}}) == 100.0
    assert _read({"UPDATE_PAD_FRESH": {"count": 5, "ms": 0.0}}) == 0.0


def test_it_is_an_entry_found_by_name_with_its_cell(root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", NAME)
    entries.check_entry(root, bench, "per_layer", metric)
    assert "mperf16m.rows" in metric["workloads"]
    assert (metric["unit"], metric["better"]) == ("%", "higher")
    assert (metric["source"], metric["layer"], metric["moves"]) \
        == ("program_span", "server actor", "rows_per_s")

"""The reader PR 48 entered, `client.reply_pieced_share.rows`: PR 48's pair
of counters (GET_REPLY_ROWS_PIECED, GET_REPLY_ROWS_WHOLE: one a host row
reply shard `MatrixWorker.process_reply_get` handed its sink) on a
hand-built ``Observations``: a percentage from the counts, nothing where
neither counted, and nothing, without an exception, from a program that has
no such counter (the parent commit, which the driver runs it on too).
(ISSUE 48 asked for this case in `test_scope_metrics.py`; a file the
benchmark already has is not this PR's to edit, so it is a file of its own,
as PR 38's is.)"""

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

NAME = "client.reply_pieced_share.rows"

COUNTERS = {
    "GET_REPLY_ROWS_PIECED": {"count": 588, "ms": 0.0},
    "GET_REPLY_ROWS_WHOLE": {"count": 12, "ms": 0.0},
    "GET_REPLY_ROWS_DIRECT": {"count": 600, "ms": 0.0},
    "BLOB_D2H": {"count": 600, "ms": 7800.0},
    "CLIENT_PLACE_ROWS": {"count": 600, "ms": 1200.0},
}
#: what the parent's program counts on a reply's way through the worker
PARENT = ("GET_REPLY_ROWS_DIRECT", "BLOB_D2H", "CLIENT_PLACE_ROWS")


class _Window:
    def __init__(self, counters):
        self.counters, self.rounds, self.seconds = counters, 600, 20.0


def _read(counters):
    return load_module("metrics", NAME).read(
        Observations(window=_Window(counters)))


@pytest.mark.parametrize("pieced, whole, want", [
    (588, 12, 98.0), (600, 0, 100.0), (0, 600, 0.0), (0, 0, None)])
def test_reader(pieced, whole, want):
    counters = dict(COUNTERS,
                    GET_REPLY_ROWS_PIECED={"count": pieced, "ms": 0.0},
                    GET_REPLY_ROWS_WHOLE={"count": whole, "ms": 0.0})
    got = _read(counters)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_parent_s_monitors_alone_give_nothing():
    assert _read({k: COUNTERS[k] for k in PARENT}) is None
    assert _read({}) is None


def test_one_counter_alone_is_a_share_too():
    """A window in which every reply left in pieces has no
    GET_REPLY_ROWS_WHOLE entry at all (a counter exists from its first
    count)."""
    assert _read({"GET_REPLY_ROWS_PIECED": {"count": 5, "ms": 0.0}}) == 100.0
    assert _read({"GET_REPLY_ROWS_WHOLE": {"count": 5, "ms": 0.0}}) == 0.0


def test_it_is_an_entry_found_by_name_with_its_cell(root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", NAME)
    entries.check_entry(root, bench, "per_layer", metric)
    assert "mperf16m.rows" in metric["workloads"]
    assert (metric["unit"], metric["better"]) == ("%", "higher")
    assert (metric["source"], metric["layer"], metric["moves"]) \
        == ("program_span", "worker actor and client", "rows_per_s")

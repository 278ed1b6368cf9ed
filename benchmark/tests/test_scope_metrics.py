"""The seven readers PR 31 entered, each on a hand-built
``Observations``: the trace's scopes, the window's counters and its
opening snapshot in, the value out; nothing where there is nothing to
read, and never a zero in its place."""

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

TRACE = {
    "window_s": 3.0, "collective_s": 0.132,
    "scopes": {
        "jit_rows_padded": {"mv.update.scatter_add": 0.996,
                            "mv.update.dedup": 0.016, "no-scope": 0.01},
        "jit_group": {"mv.update.scatter_add": 0.204},
        "jit__lambda": {"mv.table.gather": 0.1368},
        "jit_step": {"mv.sgns.step": 0.2544}}}
COUNTERS = {"UPDATE_ROWS_FAST": {"count": 6356, "ms": 0.0},
            "UPDATE_ROWS_XLA": {"count": 0, "ms": 0.0}}
AT_OPEN = {"TABLE_INIT": {"count": 1, "elapsed_ms": 612.5},
           "SERVER_PROCESS_GET": {"count": 48, "elapsed_ms": 30.0}}


class _Window:
    def __init__(self, rounds=0, counters=None, at_open=None):
        self.rounds, self.counters = rounds, counters or {}
        self.at_open = at_open or {}


def _obs(trace=TRACE, rounds=240, counters=COUNTERS, at_open=AT_OPEN):
    return Observations(trace=trace, traced=_Window(rounds),
                        window=_Window(3178, counters, at_open))


def _read(name, obs):
    return load_module("metrics", name).read(obs)


WANT = {
    "device.collective_share.train": 4.4,
    "table.scatter_ms_per_round.train": 1200.0 / 240,   # both programs
    "table.gather_ms_per_round.train": 0.57,
    "trainer.step_ms_per_round.train": 1.06,
    "table.update_fast_share.train": 100.0,
    "table.update_fast_share.rows": 100.0,
    "setup.table_init_s": 0.6125,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader(name, root):
    """The reader as the root's `BENCHMARK.json` names it, found by name
    wherever its entry stands."""
    entries.named(entries.bench_of(root), "per_layer", name)
    assert entries.reader_of(root, name).read(_obs()) \
        == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", [
    "device.collective_share.train", "table.scatter_ms_per_round.train",
    "table.gather_ms_per_round.train", "trainer.step_ms_per_round.train"])
def test_a_trace_reader_reads_nothing_without_a_trace_or_its_scope(name):
    assert _read(name, _obs(trace=None)) is None
    if "per_round" in name:
        assert _read(name, _obs(rounds=0)) is None
        # a program whose operations do not carry the scope: no number
        bare = dict(TRACE, scopes={"jit_group": {"no-scope": 3.0}})
        assert _read(name, _obs(trace=bare)) is None


@pytest.mark.parametrize("name", ["table.update_fast_share.train",
                                  "table.update_fast_share.rows"])
def test_the_path_share_counts_both_paths(name):
    mixed = {"UPDATE_ROWS_FAST": {"count": 30, "ms": 0.0},
             "UPDATE_ROWS_XLA": {"count": 10, "ms": 0.0}}
    assert _read(name, _obs(counters=mixed)) == pytest.approx(75.0)
    only_xla = {"UPDATE_ROWS_XLA": {"count": 10, "ms": 0.0}}
    assert _read(name, _obs(counters=only_xla)) == 0.0
    # no scatter-add in the window, or a program without the counters
    none = {"UPDATE_ROWS_FAST": {"count": 0, "ms": 0.0},
            "UPDATE_ROWS_XLA": {"count": 0, "ms": 0.0}}
    assert _read(name, _obs(counters=none)) is None
    assert _read(name, _obs(counters={"TABLE_WAIT": {"count": 1}})) is None


def test_the_reply_share_reads_100_0_and_nothing():
    """`client.reply_direct_share.rows` (PR 33's counters): every shard
    of a reply copied straight in, every one searched for, and a window
    without a host Get (the device cell's) or a program without the
    counters."""
    name = "client.reply_direct_share.rows"
    direct = {"GET_REPLY_ROWS_DIRECT": {"count": 210, "ms": 0.0},
              "GET_REPLY_ROWS_PLACED": {"count": 0, "ms": 0.0}}
    assert _read(name, _obs(counters=direct)) == 100.0
    placed = {"GET_REPLY_ROWS_DIRECT": {"count": 0, "ms": 0.0},
              "GET_REPLY_ROWS_PLACED": {"count": 35, "ms": 0.0}}
    assert _read(name, _obs(counters=placed)) == 0.0
    both = {"GET_REPLY_ROWS_DIRECT": {"count": 30, "ms": 0.0},
            "GET_REPLY_ROWS_PLACED": {"count": 10, "ms": 0.0}}
    assert _read(name, _obs(counters=both)) == pytest.approx(75.0)
    none = {"GET_REPLY_ROWS_DIRECT": {"count": 0, "ms": 0.0},
            "GET_REPLY_ROWS_PLACED": {"count": 0, "ms": 0.0}}
    assert _read(name, _obs(counters=none)) is None
    assert _read(name, _obs(counters={"TABLE_WAIT": {"count": 9}})) is None


def test_table_init_reads_set_up_and_not_the_window():
    # counted in the window only (a table made later): not set-up's
    late = _obs(counters={"TABLE_INIT": {"count": 1, "ms": 600.0}},
                at_open={})
    assert _read("setup.table_init_s", late) is None
    zero = {"TABLE_INIT": {"count": 0, "elapsed_ms": 0.0}}
    assert _read("setup.table_init_s", _obs(at_open=zero)) is None

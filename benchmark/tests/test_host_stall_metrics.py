"""The six readers PR 52 entered, of the program's heartbeat
(`multiverso_tpu/runtime/thread_roles.py`; docs/OBSERVABILITY.md
"Stalls"): HOST_STALL and HOST_STALL_FROZEN's milliseconds over the
window's seconds, HOST_BEAT_LATE's over its count, each for the training
cells and for the rows cells. On a hand-built ``Observations``: a value,
0.0 from a window whose beats counted and that held no stall, and
nothing, without an exception, from a program that has no heartbeat (the
parent commit, which the driver runs them on too)."""

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

#: name -> (unit, what a window of 20 s reads with one frozen stall of
#: 112 ms and one blocked stretch of 300 ms in 2,000 beats 0.2 ms late)
WANT = {"host.stall_ms_per_s": ("ms/s", 20.6),
        "host.frozen_ms_per_s": ("ms/s", 5.6),
        "host.beat_late_ms": ("ms", 0.2)}
KINDS = {"train": "words_per_s", "rows": "rows_per_s"}
NAMES = [f"{name}.{kind}" for name in WANT for kind in KINDS]

STALLED = {"HOST_BEAT_LATE": {"count": 2000, "ms": 400.0},
           "HOST_STALL": {"count": 2, "ms": 412.0},
           "HOST_STALL_FROZEN": {"count": 1, "ms": 112.0},
           "TABLE_WAIT": {"count": 7000, "ms": 15000.0}}
#: a stall monitor exists from its first record: a clean window of a
#: process that has had none lacks the names, one that had has them at 0
CLEAN = [{"HOST_BEAT_LATE": {"count": 2000, "ms": 0.0}},
         {"HOST_BEAT_LATE": {"count": 2000, "ms": 0.0},
          "HOST_STALL": {"count": 0, "ms": 0.0},
          "HOST_STALL_FROZEN": {"count": 0, "ms": 0.0}}]
PARENT = {"TABLE_WAIT": {"count": 7000, "ms": 15000.0}}


class _Window:
    def __init__(self, counters):
        self.counters, self.rounds, self.seconds = counters, 3500, 20.0


def _read(name, counters):
    return load_module("metrics", name).read(
        Observations(window=_Window(counters)))


@pytest.mark.parametrize("name", NAMES)
def test_a_value_zero_and_nothing(name):
    assert _read(name, STALLED) == pytest.approx(WANT[name.rsplit(".", 1)[0]][1])
    for clean in CLEAN:
        assert _read(name, clean) == 0.0
    assert _read(name, PARENT) is None and _read(name, {}) is None
    # beats that did not count in THIS window are no reading either
    assert _read(name, {"HOST_BEAT_LATE": {"count": 0, "ms": 0.0},
                        "HOST_STALL": {"count": 0, "ms": 0.0}}) is None


@pytest.mark.parametrize("name", NAMES)
def test_it_is_an_entry_found_by_name_with_its_cells(name, root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", name)
    entries.check_entry(root, bench, "per_layer", metric)
    stem, kind = name.rsplit(".", 1)
    moved = entries.named(bench, "end_to_end", KINDS[kind])
    assert metric["moves"] == KINDS[kind]
    # every cell that reports what it moves, as the accepted file had them
    assert set(metric["workloads"]) >= {
        "train": {"sgns8m.ps", "sgns8m.local", "sgns21m-x4.ps",
                  "st21b.ps-8k", "sdar30b.ps-bd4k", "xing29b.ps-4k",
                  "laguna33b.ps-8k", "keye30b.ps-16k"},
        "rows": {"mperf16m.rows", "mperf16m.rows-dev"}}[kind]
    assert set(metric["workloads"]) <= set(moved["workloads"])
    assert (metric["unit"], metric["better"]) == (WANT[stem][0], "lower")
    assert (metric["source"], metric["layer"]) \
        == ("program_counter", "host process")


def test_the_monitors_they_read_are_the_program_s():
    from multiverso_tpu.util.dashboard import METRIC_NAMES
    assert {"HOST_BEAT_LATE", "HOST_STALL",
            "HOST_STALL_FROZEN"} <= set(METRIC_NAMES)

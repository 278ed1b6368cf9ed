"""The reader PR 49 entered, `trainer.experts_short_share.lm`: PR 49's pair
of counters (LM_EXPERTS_SHORT, LM_EXPERTS_FULL: one a sparse layer a
sequence, `PSLMTrainer._count_stats`) on a hand-built ``Observations``: a
percentage from the counts, nothing where neither counted (a cell without a
sparse layer), and nothing, without an exception, from a program that has
no such counter (the parent commit, which the driver runs it on too)."""

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

NAME = "trainer.experts_short_share.lm"

#: a window of 24 steps of two sequences through six sparse layers
COUNTERS = {
    "LM_STEP": {"count": 24, "ms": 20000.0},
    "LM_TOKENS": {"count": 24 * 2 * 4096, "ms": 0.0},
    "LM_HELD_ASSIGNMENTS": {"count": 3900000, "ms": 0.0},
    "LM_EXPERT_MAX_TOKENS": {"count": 1200000, "ms": 0.0},
    "LM_EXPERTS_SHORT": {"count": 282, "ms": 0.0},
    "LM_EXPERTS_FULL": {"count": 6, "ms": 0.0},
}
#: what the parent's trainer counts of a step's experts
PARENT = ("LM_STEP", "LM_TOKENS", "LM_HELD_ASSIGNMENTS",
          "LM_EXPERT_MAX_TOKENS")


class _Window:
    def __init__(self, counters):
        self.counters, self.rounds, self.seconds = counters, 24, 20.0


def _read(counters):
    return load_module("metrics", NAME).read(
        Observations(window=_Window(counters)))


@pytest.mark.parametrize("short, full, want", [
    (282, 6, 100.0 * 282 / 288), (288, 0, 100.0), (0, 288, 0.0),
    (0, 0, None)])
def test_reader(short, full, want):
    counters = dict(COUNTERS,
                    LM_EXPERTS_SHORT={"count": short, "ms": 0.0},
                    LM_EXPERTS_FULL={"count": full, "ms": 0.0})
    got = _read(counters)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_parent_s_counters_alone_give_nothing():
    assert _read({k: COUNTERS[k] for k in PARENT}) is None
    assert _read({}) is None


def test_one_counter_alone_is_a_share_too():
    """A window in which every sequence took the short buffer has no
    LM_EXPERTS_FULL entry at all (a counter exists from its first count)."""
    assert _read({"LM_EXPERTS_SHORT": {"count": 5, "ms": 0.0}}) == 100.0
    assert _read({"LM_EXPERTS_FULL": {"count": 5, "ms": 0.0}}) == 0.0


def test_it_is_an_entry_found_by_name_with_its_cells(root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", NAME)
    entries.check_entry(root, bench, "per_layer", metric)
    # by membership: later cells were appended to the list
    assert {"st21b.ps-8k", "sdar30b.ps-bd4k", "xing29b.ps-4k",
            "laguna33b.ps-8k"} <= set(metric["workloads"])
    assert (metric["unit"], metric["better"]) == ("%", "higher")
    load = entries.named(bench, "per_layer",
                         "trainer.expert_load_max_over_mean.lm")
    assert all(metric[k] == load[k]
               for k in ("source", "layer", "moves", "workloads"))

"""The reader PR 55 entered, `trainer.kda_scan_kernel_share.lm`: PR 55's
pair of counters (LM_KDA_SCAN_KERNEL, LM_KDA_SCAN_PLAIN: one a delta layer
a sequence, `PSLMTrainer._count_stats`) on a hand-built ``Observations``:
100 from a window with the kernels' counts alone, a share from both,
nothing where neither counted (a model with no delta layer), and nothing,
without an exception, from a program that has no such counter (the parent
commit, which the driver runs it on too)."""

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

NAME = "trainer.kda_scan_kernel_share.lm"

#: a window of 15 steps of two sequences through four delta layers
COUNTERS = {
    "LM_STEP": {"count": 15, "ms": 20000.0},
    "LM_TOKENS": {"count": 15 * 2 * 8192, "ms": 0.0},
    "LM_KDA_TOKENS": {"count": 15 * 4 * 2 * 8192, "ms": 0.0},
    "LM_KDA_CHUNKS": {"count": 15 * 4 * 2 * 128, "ms": 0.0},
    "LM_KDA_SCAN_KERNEL": {"count": 120, "ms": 0.0},
}
#: what the parent's trainer counts of a step
PARENT = ("LM_STEP", "LM_TOKENS", "LM_KDA_TOKENS", "LM_KDA_CHUNKS")


class _Window:
    def __init__(self, counters):
        self.counters, self.rounds, self.seconds = counters, 15, 20.0


def _read(counters):
    return load_module("metrics", NAME).read(
        Observations(window=_Window(counters)))


@pytest.mark.parametrize("kernel, plain, want", [
    (120, None, 100.0), (90, 30, 75.0), (None, 120, 0.0), (0, 0, None),
    (None, None, None)])
def test_reader(kernel, plain, want):
    """A counter exists from its first count: a window all in kernels has
    no LM_KDA_SCAN_PLAIN entry at all."""
    counters = {k: COUNTERS[k] for k in PARENT}
    for name, n in (("LM_KDA_SCAN_KERNEL", kernel),
                    ("LM_KDA_SCAN_PLAIN", plain)):
        if n is not None:
            counters[name] = {"count": n, "ms": 0.0}
    got = _read(counters)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_parent_s_counters_alone_give_nothing():
    assert _read({k: COUNTERS[k] for k in PARENT}) is None
    assert _read({}) is None


def test_it_is_an_entry_found_by_name_with_its_cell(root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", NAME)
    entries.check_entry(root, bench, "per_layer", metric)
    # a later PR may append its cells; only delta layers count
    assert metric["workloads"][:1] == ["kimi48b.ps-8k"]
    assert (metric["unit"], metric["better"]) == ("%", "higher")
    deep = entries.named(bench, "per_layer",
                         "trainer.kda_decay_deep_share.lm")
    assert all(metric[k] == deep[k] for k in ("source", "layer", "moves"))

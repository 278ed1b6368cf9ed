"""The reader PR 53 entered, `trainer.attn_pass_fused_share.lm`: PR 53's
pair of counters (LM_ATTN_PASS_FUSED, LM_ATTN_PASS_PLAIN: one a layer a
sequence, `PSLMTrainer._count_stats`) on a hand-built ``Observations``: 100
from a window with fused counts alone, a share from both, nothing where
neither counted (latent attention), and nothing, without an exception, from
a program that has no such counter (the parent commit, which the driver
runs it on too)."""

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

NAME = "trainer.attn_pass_fused_share.lm"

#: a window of 24 steps of two sequences through five layers
COUNTERS = {
    "LM_STEP": {"count": 24, "ms": 20000.0},
    "LM_TOKENS": {"count": 24 * 2 * 8192, "ms": 0.0},
    "LM_EXPERTS_SHORT": {"count": 192, "ms": 0.0},
    "LM_ATTN_PASS_FUSED": {"count": 240, "ms": 0.0},
}
#: what the parent's trainer counts of a step
PARENT = ("LM_STEP", "LM_TOKENS", "LM_EXPERTS_SHORT")


class _Window:
    def __init__(self, counters):
        self.counters, self.rounds, self.seconds = counters, 24, 20.0


def _read(counters):
    return load_module("metrics", NAME).read(
        Observations(window=_Window(counters)))


@pytest.mark.parametrize("fused, plain, want", [
    (240, None, 100.0), (180, 60, 75.0), (None, 240, 0.0), (0, 0, None),
    (None, None, None)])
def test_reader(fused, plain, want):
    """A counter exists from its first count: a window all fused has no
    LM_ATTN_PASS_PLAIN entry at all."""
    counters = {k: COUNTERS[k] for k in PARENT}
    for name, n in (("LM_ATTN_PASS_FUSED", fused),
                    ("LM_ATTN_PASS_PLAIN", plain)):
        if n is not None:
            counters[name] = {"count": n, "ms": 0.0}
    got = _read(counters)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_parent_s_counters_alone_give_nothing():
    assert _read({k: COUNTERS[k] for k in PARENT}) is None
    assert _read({}) is None


def test_it_is_an_entry_found_by_name_with_its_cells(root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", NAME)
    entries.check_entry(root, bench, "per_layer", metric)
    # a later PR may append its cells; latent attention has no such pass
    assert metric["workloads"][:4] == ["st21b.ps-8k", "sdar30b.ps-bd4k",
                                       "laguna33b.ps-8k", "keye30b.ps-16k"]
    assert "xing29b.ps-4k" not in metric["workloads"]
    assert (metric["unit"], metric["better"]) == ("%", "higher")
    short = entries.named(bench, "per_layer",
                          "trainer.experts_short_share.lm")
    assert all(metric[k] == short[k] for k in ("source", "layer", "moves"))

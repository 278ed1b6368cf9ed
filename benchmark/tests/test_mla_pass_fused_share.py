"""The reader PR 57 entered, `trainer.mla_pass_fused_share.lm`: the pair of
counters (LM_ATTN_PASS_FUSED, LM_ATTN_PASS_PLAIN) as a trainer under LATENT
attention counts them since PR 57 (one a latent layer a sequence, the
module's layer too, `PSLMTrainer._count_stats`) on a hand-built
``Observations``: 100 from a window with fused counts alone (23 steps of
`glm30b.ps-8k`: 12 a step), 0 from plain counts alone (`xing29b.ps-4k`,
`kimi48b.ps-8k`), a share from both, and nothing, without an exception,
from a program that has no such counter (the parent commit, which counts
neither under latent attention and which the driver runs it on too)."""

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

NAME = "trainer.mla_pass_fused_share.lm"

#: a window of 23 steps of two sequences through five layers and the module
COUNTERS = {
    "LM_STEP": {"count": 23, "ms": 20000.0},
    "LM_TOKENS": {"count": 23 * 2 * 8192, "ms": 0.0},
    "LM_MTP_TOKENS": {"count": 23 * 2 * 8192, "ms": 0.0},
    "LM_EXPERTS_SHORT": {"count": 230, "ms": 0.0},
    "LM_ATTN_PASS_FUSED": {"count": 276, "ms": 0.0},
}
#: what the parent's trainer counts of a step under latent attention
PARENT = ("LM_STEP", "LM_TOKENS", "LM_MTP_TOKENS", "LM_EXPERTS_SHORT")


class _Window:
    def __init__(self, counters):
        self.counters, self.rounds, self.seconds = counters, 23, 20.0


def _read(counters):
    return load_module("metrics", NAME).read(
        Observations(window=_Window(counters)))


@pytest.mark.parametrize("fused, plain, want", [
    (276, None, 100.0), (None, 276, 0.0), (230, 46, 100 * 230 / 276),
    (0, 0, None), (None, None, None)])
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
    # a later PR may append its cells: the three that run latent.inputs
    assert metric["workloads"][:3] == ["xing29b.ps-4k", "kimi48b.ps-8k",
                                       "glm30b.ps-8k"]
    assert (metric["unit"], metric["better"]) == ("%", "higher")
    other = entries.named(bench, "per_layer",
                          "trainer.attn_pass_fused_share.lm")
    assert all(metric[k] == other[k] for k in ("layer", "moves"))
    # a cell has one kind of attention: it reports one of the two shares
    assert not set(metric["workloads"]) & set(other["workloads"])

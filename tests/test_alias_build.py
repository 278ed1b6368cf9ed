"""`build_alias` runs its pairing sweep on Python lists; the tables must
be those of Vose's sweep written on the arrays (the form it had), bit
for bit: the negatives a seed draws depend on them."""

import numpy as np
import pytest

from multiverso_tpu.models.wordembedding.model import (_alias_draw_np,
                                                       build_alias)


def _on_arrays(probs):
    probs = np.asarray(probs, np.float64)
    n = probs.size
    scaled = probs * (n / probs.sum())
    prob = np.ones(n, np.float32)
    alias = np.arange(n, dtype=np.int32)
    small = list(np.flatnonzero(scaled < 1.0)[::-1])
    large = list(np.flatnonzero(scaled >= 1.0)[::-1])
    while small and large:
        s, g = int(small.pop()), int(large.pop())
        prob[s] = scaled[s]
        alias[s] = g
        scaled[g] = scaled[g] + scaled[s] - 1.0
        (small if scaled[g] < 1.0 else large).append(g)
    return prob, alias


def _zipf_counts(n):
    return (1e6 / np.arange(1, n + 1)).astype(np.int64) + 1


@pytest.mark.parametrize("probs", [
    _zipf_counts(50_000).astype(np.float64) ** 0.75,   # a dictionary's
    np.random.default_rng(0).random(10_001),           # unsorted
    np.random.default_rng(1).random(4_096) ** 8,       # a few hold it all
    np.ones(7),                                        # nothing to pair
    np.array([5.0]),
], ids=["zipf", "unsorted", "skewed", "uniform", "one"])
def test_the_tables_are_the_array_sweeps(probs):
    prob, alias = build_alias(probs)
    want_prob, want_alias = _on_arrays(probs)
    assert prob.dtype == np.float32 and alias.dtype == np.int32
    np.testing.assert_array_equal(prob, want_prob)
    np.testing.assert_array_equal(alias, want_alias)


def test_draws_follow_the_distribution():
    probs = _zipf_counts(200).astype(np.float64) ** 0.75
    prob, alias = build_alias(probs)
    drawn = _alias_draw_np(prob, alias, np.random.default_rng(5), 400_000)
    seen = np.bincount(drawn, minlength=200) / drawn.size
    np.testing.assert_allclose(seen, probs / probs.sum(), atol=2e-3)

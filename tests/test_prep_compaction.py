"""``device_train._prep``: the epoch's subsample + stable compaction.

The program compacts in one sort that carries the tokens and their
sentence ids. These tests hold it to the formulation it replaced (a
stable argsort of the dropped flag, then three takes by the order),
written out in numpy, and to its shape as a program: one sort, the one
gather of the mask, no scatter.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.wordembedding.device_train import _prep


def _argsort_and_takes(flat, sent, keep, key):
    """The replaced formulation: the mask from the same draw, a stable
    argsort that puts kept positions first, and the takes by it."""
    draw = np.asarray(jax.random.uniform(key, flat.shape))
    mask = draw < keep[flat]
    order = np.argsort(np.where(mask, 0, 1).astype(np.int8), kind="stable")
    kept = flat[order]
    ksent = np.where(mask[order], sent[order], -1).astype(np.int32)
    return kept, ksent, np.int32(mask.sum())


def _corpus(n, vocab, seed, sentence_stride=1):
    rng = np.random.default_rng(seed)
    flat = rng.integers(0, vocab, n).astype(np.int32)
    lengths = rng.integers(1, 12, n)
    sent = np.repeat(np.arange(n, dtype=np.int32) * sentence_stride,
                     lengths)[:n]
    keep = rng.uniform(0.0, 1.0, vocab).astype(np.float32)
    return flat, sent, keep


def _with(corpus, **replaced):
    flat, sent, keep = corpus
    return flat, replaced.get("sent", sent), replaced.get("keep", keep)


# name -> (flat, sent, keep); the key's seed is the case's place here
CASES = {
    "seed0": lambda: _corpus(4096, 97, 0),
    "seed1": lambda: _corpus(4096, 97, 1),
    "all_kept": lambda: _with(_corpus(2048, 31, 2),
                              keep=np.ones(31, np.float32)),
    "all_dropped": lambda: _with(_corpus(2048, 31, 3),
                                 keep=np.zeros(31, np.float32)),
    "one_token": lambda: _corpus(1, 5, 4),
    "length_not_a_multiple_of_128": lambda: _corpus(100_003, 1009, 5),
    "sentence_ids_with_gaps": lambda: _corpus(5000, 97, 6,
                                              sentence_stride=200),
    "one_long_sentence": lambda: _with(_corpus(3000, 17, 7),
                                       sent=np.full(3000, 41, np.int32)),
}


@pytest.mark.parametrize("name", list(CASES))
def test_prep_equals_argsort_and_takes(name):
    flat, sent, keep = CASES[name]()
    key = jax.random.PRNGKey(list(CASES).index(name))
    kept, ksent, n_kept = _prep(jnp.asarray(flat), jnp.asarray(sent),
                                jnp.asarray(keep), key)
    want_kept, want_ksent, want_n = _argsort_and_takes(flat, sent, keep, key)
    assert kept.dtype == jnp.int32 and ksent.dtype == jnp.int32
    assert n_kept.dtype == jnp.int32 and int(n_kept) == int(want_n)
    np.testing.assert_array_equal(np.asarray(kept), want_kept)
    np.testing.assert_array_equal(np.asarray(ksent), want_ksent)
    if name == "all_kept":
        assert int(n_kept) == flat.size
    if name == "all_dropped":
        assert int(n_kept) == 0 and (np.asarray(ksent) == -1).all()


def _operations(text):
    """Names of the StableHLO operations in a lowered module's text, in
    either printed form (``"stablehlo.sort"(`` or ``stablehlo.sort ``)."""
    return re.findall(r'=\s*"?stablehlo\.(\w+)"?[\s(]', text)


def test_prep_lowers_to_one_sort_one_gather_no_scatter():
    flat = jnp.zeros(1000, jnp.int32)
    text = _prep.lower(flat, flat, jnp.ones(4, jnp.float32),
                       jax.random.PRNGKey(0)).as_text()
    ops = _operations(text)
    assert "add" in ops     # the reading of the text finds operations
    # keep[flat] is the one gather; a take by a sorted order would be
    # another 72M-element random read on the chip (24 ns an element)
    assert [op for op in ops if "gather" in op] == ["gather"]
    assert [op for op in ops if "scatter" in op] == []
    assert [op for op in ops if "sort" in op] == ["sort"]

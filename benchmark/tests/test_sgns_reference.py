"""The comparison that decides `correct` in the SGNS cells, on a block
made here: an exact float32 table passes, a table kept in bfloat16
fails, as it does in the rows cell."""

import jax
import ml_dtypes
import numpy as np
import pytest

from benchmark.reference import sgns_block

C, W, K, B, D, ROWS = 256, 5, 5, 8, 128, 4000
LR = np.float32(0.025)


def _block(seed=3):
    """Rows at word2vec's initial scale (inputs uniform in +-0.5/D,
    outputs trained a little), ids with duplicates, a validity mask."""
    rng = np.random.default_rng(seed)
    emb_in = ((rng.random((ROWS, D)) - 0.5) / D).astype(np.float32)
    emb_out = (rng.standard_normal((ROWS, D)) * 4e-3).astype(np.float32)
    in_ids = rng.integers(0, ROWS, C).astype(np.int32)
    out_ids = rng.integers(0, ROWS, C + 2 * W + (C // B) * K).astype(np.int32)
    pmask = (rng.random((C, 2 * W)) < 0.6).astype(np.float32)
    return emb_in, emb_out, in_ids, out_ids, pmask


def _apply(table, ids, deltas, dtype):
    """What a table stored in ``dtype`` holds after an Add of ``deltas``
    at ``ids`` (duplicates summed, as the scatter-add sums them)."""
    table = table.astype(dtype)
    total = np.zeros(table.shape, np.float32)
    np.add.at(total, ids, np.asarray(deltas, np.float32))
    return table, (table.astype(np.float32) + total).astype(dtype)


@pytest.mark.parametrize("dtype, passes", [(np.float32, True),
                                          (ml_dtypes.bfloat16, False)])
def test_the_block_comparison_holds_the_table_to_float32(dtype, passes):
    emb_in, emb_out, in_ids, out_ids, pmask = _block()
    n_band = C + 2 * W
    # the table's rows as the block reads them, in the table's own type
    v = emb_in.astype(dtype).astype(np.float32)[in_ids]
    u = emb_out.astype(dtype).astype(np.float32)[out_ids]
    with jax.default_matmul_precision("highest"):
        loss, d_v, d_band, d_neg, _ = sgns_block.reference_block(
            v, u[:n_band], u[n_band:].reshape(C // B, K, D), pmask, LR)
    d_u = np.concatenate([np.asarray(d_band),
                          np.asarray(d_neg).reshape(-1, D)])
    before_in, after_in = _apply(emb_in, in_ids, d_v, dtype)
    before_out, after_out = _apply(emb_out, out_ids, d_u, dtype)
    wrong = sgns_block.compare(
        before_in.astype(np.float32)[in_ids],
        before_out.astype(np.float32)[out_ids],
        after_in.astype(np.float32)[in_ids],
        after_out.astype(np.float32)[out_ids],
        in_ids, out_ids, pmask, LR, float(loss), W, K, B)
    assert (wrong == []) is passes, wrong
    if not passes:
        assert any("rows' change" in w for w in wrong)

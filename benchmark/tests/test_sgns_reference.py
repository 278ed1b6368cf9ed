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
def test_the_block_comparison_holds_the_table_to_float32(dtype, passes,
                                                         capsys):
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
    compared = {}
    wrong = sgns_block.compare(
        before_in.astype(np.float32)[in_ids],
        before_out.astype(np.float32)[out_ids],
        after_in.astype(np.float32)[in_ids],
        after_out.astype(np.float32)[out_ids],
        in_ids, out_ids, pmask, LR, float(loss), W, K, B, compared)
    assert (wrong == []) is passes, wrong
    # every number compared, beside its limit
    assert {k: limit for k, (_, limit) in compared.items()} == {
        "loss_rel": sgns_block.LOSS_RTOL,
        "input_change_rel": sgns_block.CHANGE_RTOL,
        "output_change_rel": sgns_block.CHANGE_RTOL}
    assert all(value <= limit for value, limit in compared.values()) is passes
    explained = [line for line in capsys.readouterr().out.splitlines()
                 if "logits nearest the clip" in line]
    if passes:
        assert explained == []
    else:
        assert any("rows' change" in w for w in wrong)
        # a failed comparison explains itself: ten rows a failed table
        tables = {w.split()[2] for w in wrong if "rows' change" in w}
        assert len(explained) == 10 * len(tables)
        assert all("from the clip, rounding bound" in line
                   for line in explained)


def test_the_failure_log_finds_the_pairs_of_a_row():
    """The logits the log prints are those of the pairs a row takes part
    in: as a center, as a context in the band, as a shared negative."""
    rng = np.random.default_rng(5)
    v = rng.standard_normal((C, D))
    u_band = rng.standard_normal((C + 2 * W, D))
    u_neg = rng.standard_normal((C // B, K, D))
    pmask = (rng.random((C, 2 * W)) < 0.6).astype(np.float32)
    pmask[8] = 0.0                       # a center with no valid context
    of_center, of_context = sgns_block._pair_logits(
        v, u_band, u_neg, pmask, W, B)
    offsets = [o for o in range(-W, W + 1) if o != 0]
    logits, bounds = of_center(3)
    want = [v[3] @ u_band[W + off + 3] for w, off in enumerate(offsets)
            if pmask[3, w]] + [v[3] @ u_neg[0, k] for k in range(K)]
    assert logits == pytest.approx(want)
    assert bounds == pytest.approx(
        [np.abs(v[3] * u_band[W + off + 3]).sum() / 256
         for w, off in enumerate(offsets) if pmask[3, w]]
        + [np.abs(v[3] * u_neg[0, k]).sum() / 256 for k in range(K)])
    assert of_center(8)[0].size == 0     # its negatives weigh nothing
    j = 40                               # a band row: contexts of 10 centers
    logits, _ = of_context(j)
    assert logits == pytest.approx(
        [v[j - W - off] @ u_band[j] for w, off in enumerate(offsets)
         if pmask[j - W - off, w]])
    logits, _ = of_context(C + 2 * W + 1 * K + 2)    # negative (1, 2)
    assert logits == pytest.approx(
        [v[c] @ u_neg[1, 2] for c in range(B, 2 * B) if pmask[c].any()])

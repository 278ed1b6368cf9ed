"""The row-traffic generator and the numpy replay that decides
`correct` in the rows cell."""

import numpy as np
import pytest

from benchmark.lib.rowtraffic import RowTraffic
from benchmark.reference import rows_replay

MIX = {"ops": ["get", "add"], "ids_per_request": 500,
       "id_distribution": {"kind": "zipf", "s": 1.0}, "id_order": "sorted",
       "pool_requests": 4, "delta_pool": 3, "delta_step": 0.125, "sample_per_request": 16,
       "untouched_rows": 8}
ROWS, COLS = 50_000, 50


def _run(traffic, rounds, table_dtype=np.float32, lose_add=None):
    """A table that does what the configuration promises, or does not."""
    table = np.zeros((ROWS, COLS), table_dtype)
    log = []
    for r in range(rounds):
        q = traffic.request(r)
        ids = traffic.ids[q]
        kept = table[ids][traffic.positions[q]].astype(np.float32)
        log.append(("get", q, kept))
        if r != lose_add:
            table[ids] = (table[ids].astype(np.float32)
                          + traffic.delta(q)).astype(table_dtype)
        log.append(("add", q, None))
    return log, table[traffic.sample].astype(np.float32)


def test_every_seed_makes_requests_of_the_same_sizes():
    a, b = RowTraffic(MIX, ROWS, COLS, 1), RowTraffic(MIX, ROWS, COLS, 2**31 + 5)
    for x, y in zip(a.ids, b.ids):
        assert x.size == y.size == 500 and x.dtype == np.int32
        assert np.unique(x).size == 500 and (np.diff(x) > 0).all()
        assert not np.array_equal(x, y)
    again = RowTraffic(MIX, ROWS, COLS, 1)
    assert all(np.array_equal(x, y) for x, y in zip(a.ids, again.ids))
    assert all(np.array_equal(x, y) for x, y in zip(a.deltas, again.deltas))
    # Zipf: the head of the table is in every request
    assert all(ids[0] == 0 for ids in a.ids)


def test_an_exact_table_passes_and_the_tolerance_is_zero():
    traffic = RowTraffic(MIX, ROWS, COLS, 7)
    log, final = _run(traffic, 40)
    assert rows_replay.TOLERANCE == 0.0
    assert rows_replay.replay(traffic, log, final, COLS) == []


@pytest.mark.parametrize("fault", ["lost_add", "bfloat16_table"])
def test_a_weakened_table_fails(fault):
    traffic = RowTraffic(MIX, ROWS, COLS, 7)
    if fault == "lost_add":
        log, final = _run(traffic, 40, lose_add=3)
    else:
        import ml_dtypes
        log, final = _run(traffic, 40, table_dtype=ml_dtypes.bfloat16)
    wrong = rows_replay.replay(traffic, log, final, COLS)
    assert wrong and any("final table" in w for w in wrong)

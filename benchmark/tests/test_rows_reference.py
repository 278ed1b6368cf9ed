"""The row-traffic generator and the numpy replay that decides
`correct` in the rows cells: the host operations on a numpy table, and
the driver's device operations (`drivers/rows.py` `OnDevice`: ids,
deltas and replies as `jax.Array`s, the sampled positions kept on the
device and copied out at the end) on a `jax.numpy` one."""

import numpy as np
import pytest

from benchmark.drivers.rows import OnDevice
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


class _DeviceTable:
    """What the two device operations need of a table, kept in
    ``dtype``: `get_rows_device` and `add_rows` on `jax.Array`s."""

    def __init__(self, dtype):
        import jax.numpy as jnp
        self.dtype = dtype
        self.data = jnp.zeros((ROWS, COLS), dtype)

    def get_rows_device(self, ids):
        return self.data[ids].astype(np.float32)

    def add_rows(self, ids, delta):
        self.data = self.data.at[ids].set(
            (self.data[ids].astype(np.float32) + delta).astype(self.dtype))


def _run_on_device(traffic, rounds, table_dtype=np.float32, lose_add=None):
    """The same rounds through the driver's device operations: what
    `Driver._round` and `Driver.check` do with them, at this size."""
    table = _DeviceTable(table_dtype)
    widest = max(p.size for p in traffic.positions)
    kept = np.empty((rounds, widest, COLS), np.float32)
    on_device = OnDevice(traffic, rounds)
    log = []
    for r in range(rounds):
        q = traffic.request(r)
        assert on_device.send("get_device", table, q) == "get"
        on_device.keep(r, q)
        log.append(("get", q, kept[r, :traffic.positions[q].size]))
        if r != lose_add:
            assert on_device.send("add_device", table, q) == "add"
        log.append(("add", q, None))
    on_device.copy_out(kept)
    final = table.get_rows_device(traffic.sample)
    return log, np.asarray(final)


FORMS = {"host": _run, "device": _run_on_device}


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


@pytest.mark.parametrize("form", sorted(FORMS))
def test_an_exact_table_passes_and_the_tolerance_is_zero(form):
    traffic = RowTraffic(MIX, ROWS, COLS, 7)
    log, final = FORMS[form](traffic, 40)
    assert rows_replay.TOLERANCE == 0.0
    assert rows_replay.replay(traffic, log, final, COLS) == []


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("fault", ["lost_add", "bfloat16_table"])
def test_a_weakened_table_fails(fault, form):
    traffic = RowTraffic(MIX, ROWS, COLS, 7)
    if fault == "lost_add":
        log, final = FORMS[form](traffic, 40, lose_add=3)
    else:
        import ml_dtypes
        log, final = FORMS[form](traffic, 40,
                                 table_dtype=ml_dtypes.bfloat16)
    wrong = rows_replay.replay(traffic, log, final, COLS)
    assert wrong and any("final table" in w for w in wrong)
    # a Get after the fault already differs, not the final table alone
    assert any("(get)" in w for w in wrong)


def test_the_device_form_keeps_what_the_host_form_keeps():
    """One seed, the same ids and deltas: reply for reply the sampled
    rows that come off the device are the host form's."""
    traffic = RowTraffic(MIX, ROWS, COLS, 2**31 + 9)
    host, device = _run(traffic, 12), _run_on_device(traffic, 12)
    assert np.array_equal(host[1], device[1])
    for (op, q, kept), (op_d, q_d, kept_d) in zip(host[0], device[0]):
        assert (op, q) == (op_d, q_d)
        assert kept is None or np.array_equal(kept, kept_d)

"""Updater numerics: parity with the reference formulas (SURVEY.md §2.4).

Reference formulas validated against independent numpy implementations:
default add (ref: src/updater/updater.cpp:24-31), sgd
(ref: sgd_updater.h:15-19), momentum (ref: momentum_updater.h:17-26),
adagrad intended semantics (ref: adagrad_updater.h:23-41; see
rules.py docstring for the reference's accumulator bugs we do not clone).
"""

import numpy as np
import pytest

from multiverso_tpu.updater import (AddOption, GetOption, UpdateEngine,
                                    bucket_size, create_rule, pad_rows)
from multiverso_tpu.updater.rules import ADAGRAD_EPS


def make_engine(rule_name, shape, num_workers=2, dtype=np.float32):
    return UpdateEngine(create_rule(rule_name), shape, dtype, num_workers)


class TestOptions:
    def test_add_option_roundtrip(self):
        opt = AddOption(worker_id=3, momentum=0.9, learning_rate=0.05,
                        rho=0.2, lambda_=0.7)
        back = AddOption.from_blob(opt.to_blob())
        assert back.worker_id == 3
        assert back.momentum == pytest.approx(0.9)
        assert back.learning_rate == pytest.approx(0.05)
        assert back.rho == pytest.approx(0.2)
        assert back.lambda_ == pytest.approx(0.7)

    def test_add_option_wire_layout(self):
        # 5 slots x 4 bytes; slot 0 is an int32 (union layout,
        # ref: updater.h:53-69).
        blob = AddOption(worker_id=7).to_blob()
        assert blob.size == 20
        assert int(blob.as_array(np.int32)[0]) == 7

    def test_get_option_roundtrip(self):
        assert GetOption.from_blob(GetOption(5).to_blob()).worker_id == 5


class TestDenseRules:
    def test_default_adds(self):
        eng = make_engine("default", (8,))
        data = np.zeros(8, np.float32)
        out = eng.apply_dense(data, np.arange(8, dtype=np.float32))
        np.testing.assert_allclose(np.asarray(out), np.arange(8))

    def test_sgd_subtracts(self):
        eng = make_engine("sgd", (4,))
        out = eng.apply_dense(np.full(4, 10, np.float32),
                              np.full(4, 3, np.float32))
        np.testing.assert_allclose(np.asarray(out), np.full(4, 7.0))

    def test_momentum_smooths(self):
        eng = make_engine("momentum", (3,))
        opt = AddOption(momentum=0.5)
        data = np.zeros(3, np.float32)
        delta = np.ones(3, np.float32)
        # smooth = .5*0 + .5*1 = .5 ; data = -0.5
        data = eng.apply_dense(data, delta, opt)
        np.testing.assert_allclose(np.asarray(data), -0.5 * np.ones(3))
        # smooth = .5*.5 + .5*1 = .75 ; data = -1.25
        data = eng.apply_dense(data, delta, opt)
        np.testing.assert_allclose(np.asarray(data), -1.25 * np.ones(3))

    def test_adagrad_per_worker_state(self):
        eng = make_engine("adagrad", (2,), num_workers=2)
        opt0 = AddOption(worker_id=0, learning_rate=0.1, rho=0.1)
        data = np.zeros(2, np.float32)
        delta = np.full(2, 0.05, np.float32)
        grad = 0.05 / 0.1
        g_sqr = grad * grad
        expect = -0.1 * grad / np.sqrt(g_sqr + ADAGRAD_EPS)
        data = eng.apply_dense(data, delta, opt0)
        np.testing.assert_allclose(np.asarray(data), np.full(2, expect),
                                   rtol=1e-5)
        # Worker 1 has its own fresh accumulator -> same first step again.
        data2 = eng.apply_dense(np.zeros(2, np.float32), delta,
                                AddOption(worker_id=1, learning_rate=0.1,
                                          rho=0.1))
        np.testing.assert_allclose(np.asarray(data2), np.full(2, expect),
                                   rtol=1e-5)
        # Worker 0 again: accumulator doubled.
        data = eng.apply_dense(np.zeros(2, np.float32), delta, opt0)
        expect2 = -0.1 * grad / np.sqrt(2 * g_sqr + ADAGRAD_EPS)
        np.testing.assert_allclose(np.asarray(data), np.full(2, expect2),
                                   rtol=1e-5)

    def test_int_table_always_default(self):
        rule = create_rule("sgd", dtype=np.int32)
        assert rule.name == "default"  # ref: updater.cpp:42-45


class TestRowRules:
    def test_default_rows_scatter_add(self):
        eng = make_engine("default", (6, 3))
        data = np.zeros((6, 3), np.float32)
        rows = np.array([1, 4], np.int32)
        delta = np.ones((2, 3), np.float32)
        out = np.asarray(eng.apply_rows(data, rows, delta))
        assert out[1].sum() == 3 and out[4].sum() == 3
        assert out.sum() == 6

    def test_duplicate_rows_compound_for_add(self):
        eng = make_engine("default", (4, 2))
        out = np.asarray(eng.apply_rows(
            np.zeros((4, 2), np.float32), np.array([2, 2], np.int32),
            np.ones((2, 2), np.float32)))
        np.testing.assert_allclose(out[2], [2.0, 2.0])

    def test_momentum_rows_tracks_state(self):
        eng = make_engine("momentum", (5, 2))
        opt = AddOption(momentum=0.5)
        rows = np.array([3], np.int32)
        delta = np.ones((1, 2), np.float32)
        data = np.zeros((5, 2), np.float32)
        data = np.asarray(eng.apply_rows(data, rows, delta, opt))
        np.testing.assert_allclose(data[3], [-0.5, -0.5])
        data = np.asarray(eng.apply_rows(data, rows, delta, opt))
        np.testing.assert_allclose(data[3], [-1.25, -1.25])
        assert data[0].sum() == 0  # untouched rows

    def test_padding_rows_are_dropped(self):
        rows, delta, staged = pad_rows(
            np.array([1], np.int32), np.ones((1, 2), np.float32), num_rows=4)
        assert staged is None and delta.shape == (bucket_size(1), 2)
        assert len(rows) == bucket_size(1)
        assert (rows[1:] == 4).all()  # out-of-range sentinel
        eng = make_engine("default", (4, 2))
        out = np.asarray(eng.apply_rows(np.zeros((4, 2), np.float32),
                                        np.array([1], np.int32),
                                        np.ones((1, 2), np.float32)))
        assert out.sum() == 2  # only the real row landed

    def test_bucket_sizes_bound_recompiles(self):
        assert bucket_size(1) == 8
        assert bucket_size(8) == 8
        assert bucket_size(9) == 16
        assert bucket_size(1000) == 1024


class TestDCASGD:
    """Delay-compensated ASGD (the reference's permanently-disabled
    updater hook, implemented for real — see DCASGDRule)."""

    def test_dense_compensation(self):
        eng = make_engine("dcasgd", (2,), num_workers=2)
        lr, lam = 0.1, 0.04
        opt = AddOption(worker_id=0, learning_rate=lr, lambda_=lam)
        data = np.full(2, 1.0, np.float32)
        delta = np.full(2, 0.05, np.float32)  # = lr * g, g = 0.5
        g = 0.05 / lr
        # First push: backup[0] is zeros -> compensation vs origin.
        expect = 1.0 - (0.05 + lr * lam * g * g * (1.0 - 0.0))
        data = eng.apply_dense(data, delta, opt)
        np.testing.assert_allclose(np.asarray(data), np.full(2, expect),
                                   rtol=1e-6)
        # Second push from the SAME worker: backup == current params, so
        # zero staleness -> plain sgd step.
        prev = float(np.asarray(data)[0])
        data = eng.apply_dense(data, delta, opt)
        np.testing.assert_allclose(np.asarray(data),
                                   np.full(2, prev - 0.05), rtol=1e-6)
        # A push from worker 1 moves params; worker 0's NEXT push now
        # sees nonzero staleness and compensates.
        data = eng.apply_dense(data, delta,
                               AddOption(worker_id=1, learning_rate=lr,
                                         lambda_=lam))
        w = float(np.asarray(data)[0])
        bak0 = prev - 0.05  # worker 0's backup after its second push
        expect = w - (0.05 + lr * lam * g * g * (w - bak0))
        data = eng.apply_dense(data, delta, opt)
        np.testing.assert_allclose(np.asarray(data), np.full(2, expect),
                                   rtol=1e-6)

    def test_rows_match_dense(self):
        lr, lam = 0.2, 0.1
        opt = AddOption(worker_id=0, learning_rate=lr, lambda_=lam)
        dense_eng = make_engine("dcasgd", (4, 3), num_workers=1)
        rows_eng = make_engine("dcasgd", (4, 3), num_workers=1)
        data_d = np.arange(12, dtype=np.float32).reshape(4, 3)
        data_r = data_d.copy()
        full_delta = np.zeros((4, 3), np.float32)
        rows = np.array([1, 3], np.int32)
        full_delta[rows] = 0.06
        data_d = dense_eng.apply_dense(data_d, full_delta, opt)
        data_r = rows_eng.apply_rows(data_r, rows,
                                     np.full((2, 3), 0.06, np.float32),
                                     opt)
        # Untouched rows see zero delta AND zero grad -> identical; the
        # dense path also rewrites its backup for untouched rows, which
        # only matters for later staleness, so compare the data only.
        np.testing.assert_allclose(np.asarray(data_d), np.asarray(data_r),
                                   rtol=1e-6)

    def test_rows_duplicates_compound_like_sgd(self):
        # Duplicate row ids in one Add must compound their deltas (the
        # scatter-add semantics sgd has); the compensation is evaluated
        # once against the pre-update rows.
        lr = 0.1
        opt = AddOption(worker_id=0, learning_rate=lr, lambda_=0.0)
        eng = make_engine("dcasgd", (4, 2), num_workers=1)
        data = np.ones((4, 2), np.float32)
        rows = np.array([3, 3, 3], np.int32)
        delta = np.full((3, 2), 0.05, np.float32)
        data = eng.apply_rows(data, rows, delta, opt)
        # lambda=0 -> pure sgd: three deltas land on row 3.
        np.testing.assert_allclose(np.asarray(data)[3],
                                   np.full(2, 1.0 - 3 * 0.05), rtol=1e-6)

    def test_momentum_sgd_alias(self):
        assert create_rule("momentum_sgd").name == "momentum"


# -- the sorted-runs row scatter-add (updater/row_scatter.py) ----------
#
# On a TPU the rows form's scatter-add sorts the ids, sums the deltas of
# equal ids and writes every row once (rules.fast_rows picks the path).
# Here the same kernel runs in Pallas' interpreter on the CPU and is
# held to XLA's scatter: bit for bit where every id is named once,
# within float32 rounding where ids repeat (the sum's order differs);
# and bit for bit, everywhere, to the sums in the order it states.

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from multiverso_tpu.updater import row_scatter, rules  # noqa: E402
from multiverso_tpu.util import dashboard  # noqa: E402

ROWS, COLS = 3000, 128
TILE = row_scatter.TILE


def _run_across_a_tile(rng):
    """A run of 100 equal ids whose sorted positions straddle the
    kernel's first tile boundary."""
    low = rng.permutation(1500)[:TILE - 50]
    high = 1600 + rng.permutation(1000)[:400]
    return rng.permutation(np.concatenate([low, np.full(100, 1550), high]))


RUNS_CASES = {
    # name: (ids from a generator, bit-equal expected)
    "duplicates": (lambda rng: rng.integers(0, 400, 1500), False),
    "distinct_unsorted": (lambda rng: rng.permutation(ROWS)[:1500], True),
    "distinct_sorted": (lambda rng: np.sort(rng.permutation(ROWS)[:1500]),
                        True),
    "rank_2": (lambda rng: rng.integers(0, 200, (3, 500)), False),
    "rank_2_distinct": (lambda rng: rng.permutation(ROWS)[:1500].reshape(
        5, 300), True),
    "out_of_range_dropped": (lambda rng: rng.permutation(2 * ROWS)[:2000],
                             True),
    "negative_ids_wrap_once": (lambda rng: rng.permutation(ROWS)[:1200]
                               - ROWS, True),
    "far_negative_ids_dropped": (lambda rng: np.concatenate(
        [rng.permutation(ROWS)[:1000], np.full(30, -ROWS - 7)]), True),
    "all_out_of_range": (lambda rng: np.full(1100, ROWS), True),
    "run_across_a_tile": (_run_across_a_tile, False),
    "one_long_run": (lambda rng: np.full(2 * TILE + 5, 17), False),
    # 19,000 sorted positions are two chunks of the kernel; six draws a
    # row, so a run lies across the chunks' boundary (the carry).
    "more_than_a_chunk": (lambda rng: rng.integers(
        0, ROWS, row_scatter.CHUNK + 2616), False),
    "fewer_ids_than_a_tile": (lambda rng: rng.integers(0, 50, 40), False),
    # two tiles whose every position is a run end: no tail in their lists
    "every_position_an_end": (lambda rng: rng.permutation(ROWS)[:2 * TILE],
                              True),
    # each tile is one run and its last position the only end
    "a_run_ends_with_its_tile": (lambda rng: rng.permutation(
        np.repeat([9, 2077], TILE)), False),
}


def _table_and_delta(rng, ids):
    table = rng.normal(size=(ROWS, COLS)).astype(np.float32)
    delta = rng.normal(size=ids.shape + (COLS,)).astype(np.float32)
    return table, delta


def _interpreted(table, ids, delta, mesh=None):
    """rules.scatter_add's fast form with the kernel interpreted."""
    flat = jnp.asarray(ids, jnp.int32).reshape(-1)
    flat = jnp.where(flat < 0, flat + table.shape[0], flat)
    return row_scatter.scatter_add(
        jnp.asarray(table), flat, jnp.asarray(delta).reshape(-1, COLS),
        mesh, interpret=True)


@pytest.mark.parametrize("case", sorted(RUNS_CASES))
def test_sorted_runs_scatter_add_equals_xlas(case):
    make_ids, exact = RUNS_CASES[case]
    rng = np.random.default_rng(28)
    ids = np.asarray(make_ids(rng), np.int32)
    table, delta = _table_and_delta(rng, ids)
    want = np.asarray(jnp.asarray(table).at[ids].add(delta, mode="drop"))
    got = np.asarray(jax.jit(_interpreted)(table, ids, delta))
    if case == "more_than_a_chunk":
        at = np.sort(ids)
        assert at[row_scatter.CHUNK - 1] == at[row_scatter.CHUNK]
    if exact:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        once = np.setdiff1d(np.arange(ROWS), ids[ids >= 0])
        np.testing.assert_array_equal(got[once], want[once])


def _stated_sums(table, ids, delta):
    """``row + (d1 + d2 + ...)`` for every row named, its deltas in the
    order of their positions, in float32: what the sorted-runs form has
    given since it was written, one step of numpy at a time."""
    flat = ids.reshape(-1)
    flat = np.where(flat < 0, flat + ROWS, flat)
    sums = {}
    for row, d in zip(flat.tolist(), delta.reshape(-1, COLS)):
        if 0 <= row < ROWS:
            sums[row] = sums[row] + d if row in sums else d
    want = table.copy()
    for row, acc in sums.items():
        want[row] = table[row] + acc
    return want


@pytest.mark.parametrize("devices", [1, 2, 4])
@pytest.mark.parametrize("case", sorted(RUNS_CASES))
def test_sorted_runs_scatter_add_is_the_stated_sum_bit_for_bit(
        case, devices):
    """The run ends come as a list and the reads a tile ahead: the
    sums, their order and every bit of the result stay."""
    from multiverso_tpu.sharding import mesh as meshlib
    rng = np.random.default_rng(61)
    ids = np.asarray(RUNS_CASES[case][0](rng), np.int32)
    table, delta = _table_and_delta(rng, ids)
    mesh, placed = None, table
    if devices > 1:
        mesh = meshlib.local_mesh(devices)
        placed = jax.device_put(table, meshlib.row_sharded(mesh))
    got = jax.jit(functools.partial(_interpreted, mesh=mesh))(
        placed, ids, delta)
    np.testing.assert_array_equal(np.asarray(got),
                                  _stated_sums(table, ids, delta))


def _listed_ends(ids, lo, num_rows):
    """sorted_runs' ``(n_live, ends, end_rows, counts)`` by numpy: a
    tile's run ends first and in order, then the last of them again."""
    local = np.sort(ids[(ids >= lo) & (ids < lo + num_rows)]) - lo
    pad = row_scatter.CHUNK if ids.size > row_scatter.CHUNK else TILE
    is_end = np.zeros(-(-ids.size // pad) * pad, bool)
    is_end[:local.size] = np.append(local[1:] != local[:-1], True)[
        :local.size]
    row = np.zeros(is_end.size, np.int64)
    row[:local.size] = local
    ends, end_rows, counts = [], [], []
    for tile, rows in zip(is_end.reshape(-1, TILE), row.reshape(-1, TILE)):
        (at,) = np.nonzero(tile)
        counts.append(at.size)
        at = np.append(at, np.full(TILE - at.size,
                                   at[-1] if at.size else 0))
        ends.append(at)
        end_rows.append(rows[at] if counts[-1] else 0 * at)
    return (local.size, np.concatenate(ends), np.concatenate(end_rows),
            np.array(counts))


@pytest.mark.parametrize("lo, num_rows", [(0, ROWS), (750, 750)])
@pytest.mark.parametrize("case", sorted(RUNS_CASES))
def test_sorted_runs_lists_every_tiles_run_ends(case, lo, num_rows):
    """What the kernel walks for its writes, the whole table's and a
    shard's (the second of four): a tile with no end because one run
    covers it, a tile whose every position is one, the dead tail, a run
    across a tile and a chunk."""
    ids = np.asarray(RUNS_CASES[case][0](np.random.default_rng(61)),
                     np.int32).reshape(-1)
    ids = np.where(ids < 0, ids + ROWS, ids)
    code, perm, n_live, ends, end_rows, counts = jax.jit(
        row_scatter.sorted_runs, static_argnums=2)(ids, lo, num_rows)
    want_live, want_ends, want_rows, want_counts = _listed_ends(
        ids, lo, num_rows)
    assert int(n_live) == want_live
    np.testing.assert_array_equal(ends, want_ends)
    np.testing.assert_array_equal(end_rows, want_rows)
    np.testing.assert_array_equal(counts, want_counts)
    assert ends.shape == end_rows.shape == code.shape == perm.shape
    # the listed ends are the code's ends, and the listed rows theirs
    code = np.asarray(code).reshape(-1, TILE)
    for tile, listed, rows, n in zip(code, want_ends.reshape(-1, TILE),
                                     want_rows.reshape(-1, TILE),
                                     want_counts):
        at = tile[listed[:n]]
        assert np.all(at & 1 == 1) and np.all(np.diff(at >> 2) > 0)
        np.testing.assert_array_equal(at >> 2, rows[:n])
    assert counts.sum() == np.unique(
        ids[(ids >= lo) & (ids < lo + num_rows)]).size
    if case == "one_long_run" and lo == 0:
        assert list(want_counts) == [0, 0, 1]
    if case == "every_position_an_end" and lo == 0:
        assert list(want_counts) == [TILE, TILE]


def test_a_run_sums_in_the_order_of_its_positions():
    """Not XLA's order, but a stated one: row + (d1 + d2 + ...)."""
    rng = np.random.default_rng(3)
    ids = np.full(1100, 5, np.int32)
    table, delta = _table_and_delta(rng, ids)
    acc = delta[0].copy()
    for d in delta[1:]:
        acc = acc + d
    got = np.asarray(jax.jit(_interpreted)(table, ids, delta))
    np.testing.assert_array_equal(got[5], table[5] + acc)


@pytest.mark.parametrize("devices", [2, 4])
def test_row_sharded_table_equals_the_one_device_result(devices):
    """Each device sorts the replicated ids with the rows it does not
    own keyed out, and visits its own: no collective, the same table."""
    from multiverso_tpu.sharding import mesh as meshlib
    mesh = meshlib.local_mesh(devices)
    rng = np.random.default_rng(devices)
    ids = rng.integers(-5, ROWS + 40, 2500).astype(np.int32)
    ids[:300] = 749  # a run on the last row of the first of four shards
    table, delta = _table_and_delta(rng, ids)
    sharded = jax.device_put(table, meshlib.row_sharded(mesh))
    got = jax.jit(functools.partial(_interpreted, mesh=mesh))(
        sharded, ids, delta)
    assert got.sharding.is_equivalent_to(meshlib.row_sharded(mesh), 2)
    one = np.asarray(jax.jit(_interpreted)(table, ids, delta))
    np.testing.assert_array_equal(np.asarray(got), one)
    text = jax.jit(functools.partial(_interpreted, mesh=mesh)).lower(
        sharded, ids, delta).compile().as_text()
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text


@pytest.fixture
def fast_path_on_the_cpu(monkeypatch):
    """What a test steers: the platform rules.fast_rows sees, and the
    kernel interpreted. The program has no option for either."""
    monkeypatch.setattr(rules, "_platform", lambda mesh: "tpu")
    monkeypatch.setattr(
        rules.row_scatter, "scatter_add", functools.partial(
            row_scatter.scatter_add, interpret=True))


def _path_counts():
    return tuple(dashboard.Dashboard.get(name).count for name in
                 ("UPDATE_ROWS_FAST", "UPDATE_ROWS_XLA"))


@pytest.mark.parametrize("k, fast", [
    (rules.FAST_MIN_IDS // 2, False),
    # Host ids pad to the next power-of-two bucket before the rule sees
    # them, so one id past a bucket is the next bucket's count.
    (rules.FAST_MIN_IDS // 2 + 1, True),
    (rules.FAST_MIN_IDS, True),
    (rules.FAST_MIN_IDS + 1, True),
])
@pytest.mark.parametrize("rule", ["default", "sgd"])
def test_the_id_count_picks_the_path_and_both_agree(
        fast_path_on_the_cpu, rule, k, fast):
    """50-of-128-lane deltas through the engine, around the crossover,
    with sgd's sign: the counters say which form ran."""
    rng = np.random.default_rng(k)
    ids = rng.integers(0, ROWS, k).astype(np.int32)
    delta = rng.normal(size=(k, 50)).astype(np.float32)
    table = rng.normal(size=(ROWS, COLS)).astype(np.float32)
    before = _path_counts()
    got = np.asarray(make_engine(rule, (ROWS, COLS)).apply_rows(
        jnp.asarray(table), ids, delta))
    after = _path_counts()
    assert (after[0] - before[0], after[1] - before[1]) == \
        ((1, 0) if fast else (0, 1))
    want = table.copy()
    np.add.at(want[:, :50], ids, -delta if rule == "sgd" else delta)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[:, 50:], table[:, 50:])


@pytest.mark.parametrize("shape, dtype, k, fast", [
    ((ROWS, 128), np.float32, 4096, True),
    # two tiles a row: the TPU's compiler refuses the kernel's one-row
    # DMAs on it (tests/test_row_scatter_tpu_compile.py), so XLA's scatter
    ((ROWS, 256), np.float32, 4096, False),
    ((ROWS, 50), np.float32, 4096, False),     # not whole lanes
    ((ROWS, 128), np.int32, 4096, False),      # integer table
    ((ROWS, 128), np.float64, 4096, False),
    ((ROWS,), np.float32, 4096, False),        # array table
    ((row_scatter.MAX_ROWS, 128), np.float32, 4096, False),
    ((ROWS, 128), np.float32, 8, False),       # a tiny bucket
])
def test_fast_rows_reads_only_what_is_static(monkeypatch, shape, dtype, k,
                                             fast):
    assert not rules.fast_rows(shape, dtype, k)  # this is a CPU
    monkeypatch.setattr(rules, "_platform", lambda mesh: "tpu")
    assert rules.fast_rows(shape, dtype, k) is fast


def test_bounded_foreign_device_ids_are_dropped_on_the_fast_path(
        fast_path_on_the_cpu):
    """The multi-server variant maps global ids to this shard's rows
    inside the jit; foreign rows go out of range and are never
    visited."""
    rng = np.random.default_rng(11)
    ofs, n = 1000, 1500
    ids = rng.integers(0, 4000, (2, 1024)).astype(np.int32)
    delta = rng.normal(size=(2, 1024, COLS)).astype(np.float32)
    table = rng.normal(size=(ROWS, COLS)).astype(np.float32)
    before = _path_counts()
    got = np.asarray(make_engine("default", (ROWS, COLS)).apply_rows(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(delta),
        bounds=(ofs, n)))
    assert _path_counts()[0] - before[0] == 1
    own = (ids >= ofs) & (ids < ofs + n)
    want = table.copy()
    np.add.at(want, ids[own] - ofs, delta[own])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[n:], table[n:])


def test_the_fused_add_and_gather_counts_and_reads_the_new_rows(
        fast_path_on_the_cpu):
    from multiverso_tpu.updater.engine import pad_ids
    rng = np.random.default_rng(12)
    ids = pad_ids(rng.permutation(ROWS)[:1500], ROWS)
    delta = rng.normal(size=(1500, COLS)).astype(np.float32)
    table = rng.normal(size=(ROWS, COLS)).astype(np.float32)
    before = _path_counts()
    data, values = make_engine("default", (ROWS, COLS)).apply_rows_gather(
        jnp.asarray(table), ids, delta, None, ids, COLS)
    assert _path_counts()[0] - before[0] == 1
    want = table.copy()
    want[ids[:1500]] += delta
    np.testing.assert_array_equal(np.asarray(data), want)
    np.testing.assert_array_equal(np.asarray(values)[:1500],
                                  want[ids[:1500]])


@pytest.fixture
def build_chunk_program():
    """``build(cache_dir)``: the chunk program of a ROWS x COLS table as
    a process would get it that had built none yet."""
    def build(cache_dir, rows=ROWS):
        row_scatter._chunk_program.cache_clear()
        return row_scatter._chunk_program((rows, COLS), "float32", 2048,
                                          str(cache_dir))
    yield build
    row_scatter._chunk_program.cache_clear()


def _lowers_for_the_tpu(program):
    """``(table, code, ends, end_rows, counts, rows, carry)`` at a chunk
    of two tiles."""
    shaped = jax.ShapeDtypeStruct
    ids = shaped((2048,), jnp.int32)
    text = jax.jit(program).trace(
        shaped((ROWS, COLS), np.float32), ids, ids, ids,
        shaped((2,), jnp.int32), shaped((2048, COLS), np.float32),
        shaped((8, COLS), np.float32)
    ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    return "tpu_custom_call" in text and "mv.update.scatter_add" in text


def test_a_built_chunk_program_is_read_back_without_the_kernels_source(
        build_chunk_program, tmp_path, monkeypatch):
    """The first process lowers the Pallas kernel and keeps the program
    in its compile cache; the next reads it and imports no Pallas."""
    from multiverso_tpu.updater import row_scatter_kernel
    assert _lowers_for_the_tpu(build_chunk_program(tmp_path))
    (kept,) = (tmp_path / "mv_row_scatter").iterdir()
    with monkeypatch.context() as patched:
        patched.setattr(row_scatter_kernel, "rmw_chunk", None)  # not called
        assert _lowers_for_the_tpu(build_chunk_program(tmp_path))
    # another shape is another program
    build_chunk_program(tmp_path, rows=2 * ROWS)
    assert len(list(kept.parent.iterdir())) == 2
    # a torn file is built again, in place
    kept.write_bytes(b"torn")
    assert _lowers_for_the_tpu(build_chunk_program(tmp_path))
    assert kept.stat().st_size > 1000


def test_without_a_compile_cache_nothing_is_written(
        build_chunk_program, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    assert _lowers_for_the_tpu(build_chunk_program(""))
    assert not list(tmp_path.iterdir())


def test_a_compile_cache_that_cannot_be_written_is_no_cache(
        build_chunk_program, tmp_path):
    blocked = tmp_path / "a_file_where_the_directory_would_be"
    blocked.write_text("")
    assert _lowers_for_the_tpu(build_chunk_program(blocked))

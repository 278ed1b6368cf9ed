"""Adam as a server-side rule (updater/rules.py AdamRule): its dense
form and its lazy rows form against a numpy Adam, with duplicate ids,
device keys, bounds and a row-sharded table; and what is still refused
of the other stateful rules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.sharding import mesh as meshlib
from multiverso_tpu.updater import AddOption, UpdateEngine, create_rule

B1, LR, B2, EPS = 0.9, 3e-4, 0.95, 1e-8
OPTION = AddOption(momentum=B1, learning_rate=LR, rho=B2, lambda_=EPS)
ROWS, COLS = 96, 256


class NumpyAdam:
    def __init__(self, w):
        self.w = w.astype(np.float32).copy()
        self.m, self.v, self.t = np.zeros_like(self.w), np.zeros_like(self.w), 0

    def step(self, g, rows=None):
        """``rows``: the distinct rows named, ``g`` their summed
        gradients; None = every row."""
        rows = np.arange(self.w.shape[0]) if rows is None else rows
        self.t += 1
        f = np.float32
        self.m[rows] = f(B1) * self.m[rows] + f(1 - B1) * g
        self.v[rows] = f(B2) * self.v[rows] + f(1 - B2) * g * g
        m_hat = self.m[rows] / f(1 - B1 ** self.t)
        v_hat = self.v[rows] / f(1 - B2 ** self.t)
        self.w[rows] -= f(LR) * m_hat / (np.sqrt(v_hat) + f(EPS))

    def step_rows(self, ids, g):
        ids, g = ids.reshape(-1), g.reshape(ids.size, -1)
        rows = np.unique(ids[(ids >= 0) & (ids < self.w.shape[0])])
        self.step(np.stack([g[ids == r].sum(0) for r in rows]), rows)


def _agrees(engine, data, want: NumpyAdam):
    m, v, t = engine.state
    assert int(t) == want.t
    # float32 rounding: sums near zero keep an absolute error
    np.testing.assert_allclose(np.asarray(data), want.w, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(m), want.m, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(v), want.v, rtol=1e-5, atol=1e-7)


def _start(shape=(ROWS, COLS), sharding=None, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=shape).astype(np.float32)
    engine = UpdateEngine(create_rule("adam"), shape, np.float32, 1, sharding)
    data = jnp.asarray(w) if sharding is None else jax.device_put(w, sharding)
    return rng, engine, data, NumpyAdam(w)


def test_adam_is_a_rule_beside_the_five():
    rule = create_rule("adam")
    assert rule.name == "adam" and not rule.stateless
    assert rule.sums_duplicates
    assert create_rule("adam", np.int32).name == "default"


@pytest.mark.parametrize("shape", [(ROWS, COLS), (1000,)])
def test_dense_form_against_numpy(shape):
    rng, engine, data, want = _start(shape)
    for _ in range(4):
        g = rng.normal(size=shape).astype(np.float32)
        data = engine.apply_dense(data, g, OPTION)
        want.step(g)
    _agrees(engine, data, want)


@pytest.mark.parametrize("keys", ["host", "device"])
def test_rows_form_sums_duplicates_and_leaves_other_rows(keys):
    """Lazy Adam: a row named three times in one Add gets ONE update
    from the sum; a row not named keeps its weights and its moments
    while the table's step count still advances."""
    rng, engine, data, want = _start()
    for step in range(4):
        ids = rng.integers(0, ROWS // 2, (3, 16)).astype(np.int32)
        ids[0, :3] = 5                      # a triple
        g = rng.normal(size=(3, 16, COLS)).astype(np.float32)
        if keys == "device":
            data = engine.apply_rows(data, jnp.asarray(ids), jnp.asarray(g),
                                     OPTION)
        else:
            data = engine.apply_rows(data, ids.reshape(-1),
                                     g.reshape(-1, COLS), OPTION)
        want.step_rows(ids, g)
    _agrees(engine, data, want)
    m, v, _ = engine.state
    assert not np.asarray(m)[ROWS // 2:].any()
    assert not np.asarray(v)[ROWS // 2:].any()


def test_rows_then_dense_share_one_step_count():
    rng, engine, data, want = _start()
    ids = rng.integers(0, ROWS, 40).astype(np.int32)
    g = rng.normal(size=(40, COLS)).astype(np.float32)
    data = engine.apply_rows(data, jnp.asarray(ids), jnp.asarray(g), OPTION)
    want.step_rows(ids, g)
    g = rng.normal(size=(ROWS, COLS)).astype(np.float32)
    data = engine.apply_dense(data, g, OPTION)
    want.step(g)
    _agrees(engine, data, want)


def test_bounded_device_keys_drop_foreign_rows():
    """The multi-server form: global ids, this shard's rows only."""
    rng, engine, data, want = _start()
    ofs = 1000
    ids = rng.integers(ofs - 50, ofs + ROWS + 50, (2, 64)).astype(np.int32)
    g = rng.normal(size=(2, 64, COLS)).astype(np.float32)
    data = engine.apply_rows(data, jnp.asarray(ids), jnp.asarray(g), OPTION,
                             bounds=(ofs, ROWS))
    want.step_rows(ids - ofs, g)
    _agrees(engine, data, want)


@pytest.mark.parametrize("devices", [4])
def test_row_sharded_table_and_its_moments(devices):
    """Moments sharded like the table, the step count on every device;
    the same numbers as on one device."""
    sharding = meshlib.row_sharded(meshlib.local_mesh(devices))
    rng, engine, data, want = _start(sharding=sharding)
    m, v, t = engine.state
    assert m.sharding == sharding and v.sharding == sharding
    assert len(t.sharding.device_set) == devices
    for _ in range(3):
        ids = rng.integers(0, ROWS, (2, 32)).astype(np.int32)
        g = rng.normal(size=(2, 32, COLS)).astype(np.float32)
        data = engine.apply_rows(data, jnp.asarray(ids), jnp.asarray(g),
                                 OPTION)
        want.step_rows(ids, g)
    g = rng.normal(size=(ROWS, COLS)).astype(np.float32)
    data = engine.apply_dense(data, g, OPTION)
    want.step(g)
    assert data.sharding == sharding and engine.state[0].sharding == sharding
    _agrees(engine, data, want)


@pytest.mark.parametrize("rule", ["momentum", "adagrad", "dcasgd"])
def test_device_keys_stay_refused_where_duplicates_would_be_lost(rule):
    """These write their state once a unique row from ONE duplicate's
    delta; the CHECK names the rule."""
    engine = UpdateEngine(create_rule(rule), (ROWS, COLS), np.float32, 1)
    assert not create_rule(rule).sums_duplicates
    with pytest.raises(Exception, match=rule):
        engine.apply_rows(jnp.zeros((ROWS, COLS)),
                          jnp.zeros((8,), jnp.int32),
                          jnp.zeros((8, COLS)), OPTION)


@pytest.mark.parametrize("rule", ["default", "sgd", "adam"])
def test_device_keys_are_taken_where_duplicates_sum(rule):
    engine = UpdateEngine(create_rule(rule), (ROWS, COLS), np.float32, 1)
    out = engine.apply_rows(jnp.zeros((ROWS, COLS)),
                            jnp.asarray([3, 3, 7], jnp.int32),
                            jnp.ones((3, COLS)), OPTION)
    changed = np.flatnonzero(np.asarray(out).any(axis=1))
    assert list(changed) == [3, 7]


def test_through_the_tables_whole_and_by_device_keys():
    """The same through the actors: a matrix table and an array table
    created under -updater_type=adam, whole-table device deltas and a
    device-key row Add with duplicates, read back by device Gets."""
    import multiverso_tpu as mv
    mv.init(["-updater_type=adam"])
    try:
        matrix = mv.create_matrix_table(ROWS, COLS)
        array = mv.create_array_table(500, fill=1.0)
        rng = np.random.default_rng(7)
        want_m = NumpyAdam(np.zeros((ROWS, COLS), np.float32))
        want_a = NumpyAdam(np.ones((500,), np.float32))
        assert np.array_equal(np.asarray(array.get_device()), want_a.w)
        for _ in range(2):
            g = rng.normal(size=(ROWS, COLS)).astype(np.float32)
            matrix.wait(matrix.add_async(jnp.asarray(g), OPTION))
            want_m.step(g)
            g = rng.normal(size=(500,)).astype(np.float32)
            array.wait(array.add_async(jnp.asarray(g), OPTION))
            want_a.step(g)
            ids = rng.integers(0, ROWS, (2, 24)).astype(np.int32)
            ids[1, :4] = ids[0, 0]
            g = rng.normal(size=(2, 24, COLS)).astype(np.float32)
            matrix.wait(matrix.add_rows_async(jnp.asarray(ids),
                                              jnp.asarray(g), OPTION))
            want_m.step_rows(ids, g)
        np.testing.assert_allclose(np.asarray(matrix.get_device()), want_m.w,
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(np.asarray(array.get_device()), want_a.w,
                                   rtol=1e-5, atol=1e-7)
        rows = matrix.get_rows_device(jnp.asarray([5, 5, 9], jnp.int32))
        np.testing.assert_allclose(np.asarray(rows), want_m.w[[5, 5, 9]],
                                   rtol=1e-5, atol=1e-7)
    finally:
        mv.shutdown()


def test_the_worker_refuses_device_keys_in_the_caller_s_thread():
    import multiverso_tpu as mv
    mv.init(["-updater_type=momentum"])
    try:
        table = mv.create_matrix_table(ROWS, COLS)
        with pytest.raises(Exception, match="momentum"):
            table.add_rows_async(jnp.zeros((4,), jnp.int32),
                                 jnp.zeros((4, COLS)), OPTION)
    finally:
        mv.shutdown()

"""The delta rule's scan as Pallas kernels (multiverso_tpu/models/lm/
delta_kernels.py), interpreted on the CPU at heads of 128 lanes and chunks
of 64: the outputs and the deep count against delta.scan's ``jax.numpy``
runs of chunks and against the recurrence position by position
(benchmark/reference/lm_kda_step.py), with every product in float32 (the
equations) and in bfloat16 (the rounding); each of the five gradients
against the reference's; a decay that underflows inside a chunk; the inputs
of the cell's ``scan.carry`` check, which a state kept in bfloat16 cannot
follow; and which path ``delta.scan`` takes where."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lm_kda_step as ref
from multiverso_tpu.models.lm import delta, delta_kernels, model as lm

LANES = 128
EXACT = 3e-4        # float32 products against the reference's: rounding
WRT = ["q", "k", "v", "g", "beta"]


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setattr(delta_kernels, "INTERPRET", True)


@pytest.fixture
def float32_products(monkeypatch):
    """Every product in float32, the kernels' too: what is left against
    the reference is the equations."""
    for module in (lm, delta, delta_kernels):
        monkeypatch.setattr(module, "BF16", jnp.float32)


def _relative(a, b):
    return float(jnp.linalg.norm(jnp.ravel(a - b)) / jnp.linalg.norm(b))


def _inputs(seed=0, chunks=2, heads=2, decay=(0.01, 2.0)):
    rng = np.random.default_rng(seed)
    t = chunks * delta.CHUNK

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(t, heads, LANES))) * LANES ** -0.5
    k = unit(rng.normal(size=(t, heads, LANES)))
    v = rng.normal(size=(t, heads, LANES))
    g = -rng.uniform(*decay, size=(t, heads, LANES))
    beta = rng.uniform(0.1, 0.9, size=(t, heads))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def _kernels(*args):
    return delta_kernels.scan(*args, delta.DEEP)


def _deep_by_hand(g):
    t, heads, lanes = g.shape
    sums = np.asarray(g).reshape(t // delta.CHUNK, delta.CHUNK, heads,
                                 lanes).sum(1)
    return int((sums < delta.DEEP).sum())


# -- forward ----------------------------------------------------------------------

@pytest.mark.parametrize("chunks,heads", [(2, 2), (3, 4), (4, 3)])
def test_the_kernel_is_the_recurrence_and_the_plain_scan(chunks, heads,
                                                         float32_products):
    args = _inputs(chunks, chunks, heads)
    with ref.PRECISION:
        want = ref.recurrence(*args)
        plain, deep_plain = delta.scan(*args)
        got, deep = _kernels(*args)
    assert _relative(got, want) < EXACT
    assert _relative(got, plain) < EXACT
    assert int(deep) == int(deep_plain) == _deep_by_hand(args[3]) > 0


@pytest.mark.parametrize("chunks,heads", [(2, 2), (4, 2)])
def test_in_bfloat16_products_the_kernel_is_the_recurrence_rounded(chunks,
                                                                   heads):
    """The band of test_lm_kda's rounded scan, and no further from the
    reference than the plain scan is."""
    args = _inputs(3, chunks, heads)
    with ref.PRECISION:
        want = ref.recurrence(*args)
    got, plain = _kernels(*args)[0], delta.scan(*args)[0]
    assert EXACT < _relative(got, want) < 2e-2
    assert _relative(got, want) < 1.1 * _relative(plain, want)
    assert _relative(got, plain) < 2e-3


# -- backward -----------------------------------------------------------------------

def _gradients(fn, args, cot):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * cot), (0, 1, 2, 3, 4))(*args)


@pytest.fixture(scope="module")
def exact_gradients():
    """The five gradients of one drawn cotangent through three chunks of
    two heads: ``(the kernels', the reference's)`` in float32 products."""
    patch = pytest.MonkeyPatch()
    patch.setattr(delta_kernels, "INTERPRET", True)
    for module in (lm, delta, delta_kernels):
        patch.setattr(module, "BF16", jnp.float32)
    try:
        args = _inputs(1, 3, 2)
        cot = jnp.asarray(np.random.default_rng(2).normal(
            size=args[2].shape), jnp.float32)
        with ref.PRECISION:
            return (_gradients(lambda *a: _kernels(*a)[0], args, cot),
                    _gradients(ref.recurrence, args, cot))
    finally:
        patch.undo()


@pytest.mark.parametrize("wrt", range(5), ids=WRT)
def test_a_gradient_is_the_recurrence_s(wrt, exact_gradients):
    got, want = exact_gradients
    assert got[wrt].shape == want[wrt].shape
    assert _relative(got[wrt], want[wrt]) < EXACT


@pytest.fixture(scope="module")
def rounded_gradients():
    """The same through bfloat16 products: ``(the kernels', the plain
    scan's, the reference's)``."""
    patch = pytest.MonkeyPatch()
    patch.setattr(delta_kernels, "INTERPRET", True)
    try:
        args = _inputs(5, 2, 2)
        cot = jnp.asarray(np.random.default_rng(6).normal(
            size=args[2].shape), jnp.float32)
        with ref.PRECISION:
            want = _gradients(ref.recurrence, args, cot)
        return (_gradients(lambda *a: _kernels(*a)[0], args, cot),
                _gradients(lambda *a: delta.scan(*a)[0], args, cot), want)
    finally:
        patch.undo()


@pytest.mark.parametrize("wrt", range(5), ids=WRT)
def test_in_bfloat16_a_gradient_is_as_near_as_the_plain_scan_s(
        wrt, rounded_gradients):
    got, plain, want = rounded_gradients
    assert EXACT < _relative(got[wrt], want[wrt]) < 2e-2
    assert _relative(got[wrt], want[wrt]) < 1.1 * _relative(plain[wrt],
                                                            want[wrt])


def test_a_decay_that_underflows_stays_finite_and_right(float32_products):
    """test_lm_kda's inputs at the kernels' shapes: log decays that sum to
    -700 a chunk and further, forward and every gradient."""
    q, k, v, g, beta = _inputs(4, 2, 2, decay=(0.5, 1.0))
    g = g.at[:, 0, :4].multiply(40.0)       # head 0's first four channels
    args, cot = (q, k, v, g, beta), jnp.ones_like(v)
    with ref.PRECISION:
        want = ref.recurrence(*args)
        (got, deep), pull = jax.vjp(_kernels, *args)
        grads = pull((cot, np.zeros((), jax.dtypes.float0)))
        want_grads = _gradients(ref.recurrence, args, cot)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _relative(got, want) < EXACT
    for mine, theirs in zip(grads, want_grads):
        assert bool(jnp.all(jnp.isfinite(mine)))
        assert _relative(mine, theirs) < 10 * EXACT
    assert int(deep) == _deep_by_hand(g) >= 2 * 2 * LANES
    assert float(np.asarray(g).reshape(2, 64, 2, LANES).sum(1).min()) < -1000


def test_the_carry_check_s_inputs_come_out_as_a_float32_state_gives_them():
    """benchmark/drivers/lm_kda.py ``carried``: one key, a first write of 1
    and then writes of 2^-16 with no decay over 64 chunks. A state rounded
    to bfloat16 between chunks drops every later write (3.4e-2 on the
    chip); the kernels' scratch is float32 and reads what the plain scan
    reads, under the cell's limit of 0.01."""
    heads, t = 2, 64 * delta.CHUNK
    one = jnp.zeros((t, heads, LANES), jnp.float32).at[..., 0].set(1.0)
    first = jnp.arange(t)[:, None] == 0
    v = jnp.where(first, 1.0, 2.0)[..., None] * jnp.ones((t, heads, LANES))
    beta = jnp.where(first, 1.0, 2.0 ** -16) * jnp.ones((t, heads))
    args = (one, one, v, jnp.zeros_like(one), beta)
    with ref.PRECISION:
        want = jax.jit(ref.recurrence)(*args)
    got = jax.jit(lambda *a: _kernels(*a)[0])(*args)
    plain = jax.jit(lambda *a: delta.scan(*a)[0])(*args)
    assert _relative(got, want) < 0.01
    assert _relative(got, want) < 1.1 * _relative(plain, want)


# -- the layout around them -----------------------------------------------------------

@pytest.mark.parametrize("t", [16, 12, 5])
def test_gates_and_output_on_the_tiles_view_are_a_head_at_a_time(t):
    """``delta.heads_apart`` ([T / 8, 8, H, d], or [T, 1, H, d] where 8
    does not divide T) is a view: the L2 norms, the log decay and the gated
    norm are what [T, H, d] gives, head by head."""
    heads, d = 3, 8
    cfg = type("Cfg", (), {"kda_heads_held": heads, "kda_head_dim": d,
                           "kda_beta_scale": 1, "eps": 1e-6})()
    rng = np.random.default_rng(t)
    q, k, v, f, gate = (jnp.asarray(rng.normal(size=(t, heads * d)),
                                    jnp.float32) for _ in range(5))
    b = jnp.asarray(rng.normal(size=(t, heads)), jnp.float32)
    a_log = jnp.asarray(rng.normal(size=heads), jnp.float32)
    dt_bias = jnp.asarray(rng.normal(size=heads * d), jnp.float32)
    assert delta.heads_apart(q, heads).shape == (
        (t // 8, 8, heads, d) if t % 8 == 0 else (t, 1, heads, d))
    got = delta.gates(cfg, a_log, dt_bias, q, k, v, f, b)
    assert [a.shape for a in got] == 4 * [(t, heads, d)] + [(t, heads)]

    def by_head(a):
        return np.asarray(a, np.float64).reshape(t, heads, d)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    decay = -np.exp(np.asarray(a_log, np.float64))[:, None] * by_head(
        np.logaddexp(0.0, np.asarray(f + dt_bias, np.float64)))
    want = (unit(by_head(q)) * d ** -0.5, unit(by_head(k)), by_head(v),
            decay, 1 / (1 + np.exp(-np.asarray(b, np.float64))))
    for mine, theirs in zip(got, want):
        np.testing.assert_allclose(mine, theirs, rtol=2e-5, atol=2e-6)

    norm_o = jnp.asarray(rng.normal(size=d), jnp.float32)
    wo = jnp.eye(heads * d, dtype=jnp.float32)
    with ref.PRECISION:
        out = delta.output(cfg, {"wo": wo}, {"wo": jnp.zeros_like(wo)},
                           norm_o, got[2], gate)
    o = by_head(v)
    normed = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-6) * \
        np.asarray(norm_o, np.float64)
    want = normed.reshape(t, heads * d) / (
        1 + np.exp(-np.asarray(gate, np.float64)))
    assert _relative(out, jnp.asarray(want, jnp.float32)) < 1e-2


# -- which path --------------------------------------------------------------------

@pytest.mark.parametrize("t,lanes,chunk,carry,want", [
    (128, 128, 0, jnp.float32, True),
    (8192, 128, 64, jnp.float32, True),
    (128, 128, 8, jnp.float32, False),      # the tests' chunks
    (128, 128, 16, jnp.float32, False),
    (128, 64, 0, jnp.float32, False),       # a head of half a tile
    (96, 128, 0, jnp.float32, False),       # a short sequence: one chunk
    (128, 128, 0, jnp.bfloat16, False),     # the check's control
])
def test_the_kernels_take_whole_tiles_on_a_tpu_alone(t, lanes, chunk, carry,
                                                     want, monkeypatch):
    monkeypatch.setattr(delta, "CARRY", carry)
    assert not delta.scan_in_kernels(t, lanes, lanes, chunk)   # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta.scan_in_kernels(t, lanes, lanes, chunk) is want


def test_on_the_cpu_the_scan_is_the_plain_one(monkeypatch):
    def never(*args):
        raise AssertionError("the kernels were taken on the CPU")

    monkeypatch.setattr(delta_kernels, "scan", never)
    o, deep = delta.scan(*_inputs(7, 2, 2))
    assert o.shape == (128, 2, LANES) and int(deep) >= 0


@pytest.mark.parametrize("t,name", [(128, "LM_KDA_SCAN_KERNEL"),
                                    (96, "LM_KDA_SCAN_PLAIN")])
def test_the_counter_s_name_follows_the_same_test(t, name, monkeypatch):
    cfg = type("Cfg", (), {"kda_head_dim": LANES})()
    assert delta.scan_counter(cfg, t) == "LM_KDA_SCAN_PLAIN"    # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta.scan_counter(cfg, t) == name

"""The state-space scan as Pallas kernels (multiverso_tpu/models/lm/
ssd_kernels.py), interpreted on the CPU at counts that keep the tile shapes
(4 and 8 heads of 64 lanes, a state of 128, chunks of 256): the outputs and the
deep count against ssd.scan's ``jax.numpy`` runs of chunks, in one head
group and in two, and against the recurrence position by position
(benchmark/reference/lm_granite_step.py) with every product in float32 (the
equations); each of the five gradients against both; a decay that
underflows inside a chunk; the inputs of the cell's ``scan.carry`` check,
which a state kept in bfloat16 cannot follow; the state's way from the
first chunk to the last; and ``ssd.scanned`` (the layer's part: steps, scan
and skip) through the kernels against its plain lines, and a control's
``scan`` in the kernels' place."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lm_granite_step as ref
from multiverso_tpu.models.lm import delta, model as lm, ssd, ssd_kernels

HEADS, LANES, STATE = 4, 64, 128
EXACT = 3e-4        # float32 products against the reference's: rounding
ROUNDED = 1e-1      # test_lm_granite's: bfloat16 products
NEAR = 1e-3         # the two paths: bfloat16 roundings that fall the other way
WRT = ["x", "dt", "a_log", "b", "c"]


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setattr(ssd_kernels, "INTERPRET", True)


@pytest.fixture
def float32_products(monkeypatch):
    """Every product in float32, the kernels' too: what is left against
    the reference is the equations."""
    for module in (lm, delta, ssd_kernels):
        monkeypatch.setattr(module, "BF16", jnp.float32)


def _relative(a, b):
    return float(jnp.linalg.norm(jnp.ravel(a - b)) / jnp.linalg.norm(b))


def _inputs(seed, chunks=2, heads=HEADS, dt=(1e-3, 1e-1)):
    """Steps and decays as ``ps_train.decay_init`` leaves them: about half
    the (chunk, head) pairs deep."""
    rng = np.random.default_rng(seed)
    t = chunks * ssd.CHUNK
    x = rng.normal(size=(t, heads, LANES))
    steps = np.exp(rng.uniform(*np.log(dt), (t, heads)))
    a_log = np.log(rng.uniform(1, 16, heads))
    b, c = rng.normal(size=(2, t, STATE))
    return tuple(jnp.asarray(a, jnp.float32) for a in (x, steps, a_log, b, c))


def _kernels(x, dt, a_log, b, c):
    assert ssd_kernels.shapes_fit(*x.shape, b.shape[-1], ssd.CHUNK)
    return ssd_kernels.scan(x, dt, ssd.log_decay(dt, a_log), b, c, delta.DEEP)


def _recurrence(x, dt, a_log, b, c):
    return ref.recurrence(x, dt, -jnp.exp(a_log), b, c)


def _deep_by_hand(dt, a_log):
    t, heads = dt.shape
    sums = np.asarray(ssd.log_decay(dt, a_log)).reshape(
        t // ssd.CHUNK, ssd.CHUNK, heads).sum(1)
    return int((sums < delta.DEEP).sum())


def _gradients(fn, args, cot):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * cot), (0, 1, 2, 3, 4))(*args)


# -- forward ----------------------------------------------------------------------

@pytest.mark.parametrize("chunks,heads", [(2, 4), (4, 4), (2, 8), (4, 8)])
def test_the_kernel_is_the_plain_scan(chunks, heads, monkeypatch):
    """One head group a chunk, and two (the group's columns of ``G`` rolled
    to their place): the same numbers as the plain lines to the rounding,
    and the same deep count."""
    monkeypatch.setattr(ssd_kernels, "HEADS_A_STEP", 4)
    args = _inputs(chunks, chunks, heads)
    got, deep = _kernels(*args)
    plain, deep_plain = ssd.scan(*args)
    assert _relative(got, plain) < NEAR
    assert 0 < int(deep) == int(deep_plain) == _deep_by_hand(*args[1:3])


def test_the_kernel_is_the_recurrence(float32_products):
    args = _inputs(7, 3)
    with ref.PRECISION:
        got, want = _kernels(*args)[0], _recurrence(*args)
    assert _relative(got, want) < EXACT


# -- backward -----------------------------------------------------------------------

def _both_ways(patch, seed, chunks, heads, exact):
    patch.setattr(ssd_kernels, "INTERPRET", True)
    patch.setattr(ssd_kernels, "HEADS_A_STEP", 4)
    if exact:
        for module in (lm, delta, ssd_kernels):
            patch.setattr(module, "BF16", jnp.float32)
    args = _inputs(seed, chunks, heads)
    cot = jnp.asarray(np.random.default_rng(seed + 1).normal(
        size=args[0].shape), jnp.float32)
    with ref.PRECISION:
        return tuple(_gradients(fn, args, cot) for fn in (
            lambda *a: _kernels(*a)[0], lambda *a: ssd.scan(*a)[0],
            _recurrence))


@pytest.fixture(scope="module")
def exact_gradients():
    """The five gradients of one drawn cotangent through three chunks of
    eight heads in two groups, float32 products: ``(the kernels', the plain scan's,
    the recurrence's)``."""
    patch = pytest.MonkeyPatch()
    try:
        return _both_ways(patch, 1, 3, 8, True)
    finally:
        patch.undo()


@pytest.mark.parametrize("wrt", range(5), ids=WRT)
def test_a_gradient_is_the_recurrence_s(wrt, exact_gradients):
    got, _, want = exact_gradients
    assert got[wrt].shape == want[wrt].shape
    assert _relative(got[wrt], want[wrt]) < 4 * EXACT


@pytest.fixture(scope="module")
def rounded_gradients():
    """The same through bfloat16 products, two chunks in one group."""
    patch = pytest.MonkeyPatch()
    try:
        return _both_ways(patch, 5, 2, 4, False)
    finally:
        patch.undo()


@pytest.mark.parametrize("wrt", range(5), ids=WRT)
def test_in_bfloat16_a_gradient_is_the_plain_scan_s(wrt, rounded_gradients):
    """The hand-written pull rounds where ``bdot``'s rule rounds: as near
    the plain scan's as the roundings, and no further from the recurrence
    than it is."""
    got, plain, want = rounded_gradients
    assert _relative(got[wrt], plain[wrt]) < NEAR
    assert _relative(got[wrt], want[wrt]) < ROUNDED
    assert _relative(got[wrt], want[wrt]) < 1.1 * _relative(plain[wrt],
                                                            want[wrt])


def test_a_decay_that_underflows_gives_zeros_and_finite_gradients():
    """Steps of 4 to 8 under ``A`` of 1 to 16: ``G`` passes -1,000 inside a
    chunk. Every exponent is a difference <= 0 with the mask put on it
    first: zeros, never ``0 * inf``, forward and in every gradient."""
    args = _inputs(11, 2, dt=(4.0, 8.0))
    (got, deep), pull = jax.vjp(_kernels, *args)
    grads = pull((jnp.ones_like(got), np.zeros((), jax.dtypes.float0)))
    assert int(deep) == 2 * HEADS
    plain = ssd.scan(*args)[0]
    for value in (got,) + grads:
        assert np.all(np.isfinite(np.asarray(value)))
    assert _relative(got, plain) < NEAR
    assert float(jnp.min(jnp.sum(ssd.log_decay(*args[1:3])[:ssd.CHUNK],
                                 0))) < -1000


def test_the_carry_check_s_inputs_come_out_as_a_float32_state_gives_them(
        monkeypatch):
    """benchmark/drivers/lm_granite.py ``carried``: a first write of 1 and
    then writes of half a bfloat16's last place a chunk with no decay over
    64 chunks. A state rounded to bfloat16 between chunks drops every later
    write (0.12); the kernels' scratch is float32 and reads what the plain
    scan reads, under the cell's limit of 0.01."""
    chunks = 64
    t = chunks * ssd.CHUNK
    x = jnp.ones((t, HEADS, LANES), jnp.float32)
    dt = jnp.where(jnp.arange(t)[:, None] == 0, 1.0,
                   2.0 ** -8 / ssd.CHUNK) * jnp.ones((t, HEADS))
    a_log = jnp.full((HEADS,), -30.0)
    first = jnp.zeros((t, STATE)).at[:, 0].set(1.0)
    args = (x, dt, a_log, first, first)
    with ref.PRECISION:
        want = jax.jit(_recurrence)(*args)
    got = jax.jit(lambda *a: _kernels(*a)[0])(*args)
    assert _relative(got, want) < 1e-2
    monkeypatch.setattr(ssd, "CARRY", jnp.bfloat16)
    lowered = jax.jit(lambda *a: ssd.scan(*a)[0])(*args)
    assert _relative(lowered, want) > 0.1


def test_a_cotangent_on_the_last_chunk_reaches_the_first_chunk_s_x():
    """Y's dependence on the state ENTERING a chunk, through three chunks'
    kept states: slow decays, a cotangent on the last chunk alone."""
    args = _inputs(13, 3, dt=(1e-4, 1e-3))
    cot = jnp.zeros(args[0].shape).at[2 * ssd.CHUNK:].set(1.0)
    got = _gradients(lambda *a: _kernels(*a)[0], args, cot)
    plain = _gradients(lambda *a: ssd.scan(*a)[0], args, cot)
    first = slice(0, ssd.CHUNK)
    assert float(jnp.linalg.norm(got[0][first])) \
        > 0.1 * float(jnp.linalg.norm(got[0][2 * ssd.CHUNK:]))
    # the first chunk's C reads no later state: x, dt, b there, and a_log
    for at in (0, 1, 3):
        assert _relative(got[at][first], plain[at][first]) < 2e-3
    assert _relative(got[2], plain[2]) < 2e-3


# -- ssd.scanned: the layer's part around the scan -----------------------------------------

class _Cfg:
    ssd_head_dim, ssd_state, ssd_groups, ssd_chunk = LANES, STATE, 1, 0


def _mixed(seed, chunks=2, heads=HEADS):
    """``scanned``'s five arrays: the steps' bias, a_log, the skip, the
    convolution's output [T, H P + 2 N] and the raw steps."""
    x, dt, a_log, b, c = _inputs(seed, chunks, heads)
    rng = np.random.default_rng(seed + 100)
    raw = jnp.log(jnp.expm1(dt))                # softplus's inverse
    bias = jnp.asarray(rng.normal(size=heads) * 0.1, jnp.float32)
    d = jnp.asarray(rng.normal(size=heads), jnp.float32)
    xbc = jnp.concatenate([x.reshape(x.shape[0], -1), b, c], axis=1)
    return bias, a_log, d, xbc, raw - bias


@pytest.fixture(scope="module")
def scanned_both_ways():
    """``ssd.scanned`` and its five gradients: ``(through the kernels,
    through the plain lines)``, bfloat16 products, two head groups."""
    patch = pytest.MonkeyPatch()
    patch.setattr(ssd_kernels, "INTERPRET", True)
    patch.setattr(ssd_kernels, "HEADS_A_STEP", 4)
    try:
        args = _mixed(21, heads=8)
        cot = jnp.asarray(np.random.default_rng(22).normal(
            size=(args[3].shape[0], 8 * LANES)), jnp.float32)

        def both(*a):
            (y, deep), pull = jax.vjp(lambda *a: ssd.scanned(_Cfg, *a), *a)
            return y, int(deep), pull((cot, np.zeros((), jax.dtypes.float0)))

        plain = both(*args)
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return both(*args), plain
    finally:
        patch.undo()


def test_scanned_through_the_kernels_is_its_plain_lines(scanned_both_ways):
    (got, deep, _), (plain, deep_plain, _) = scanned_both_ways
    assert _relative(got, plain) < NEAR
    assert deep == deep_plain > 0


@pytest.mark.parametrize("wrt", range(5),
                         ids=["dt_bias", "a_log", "d", "xbc", "dt"])
def test_scanned_s_gradient_is_its_plain_lines(wrt, scanned_both_ways):
    (_, _, got), (_, _, plain) = scanned_both_ways
    assert got[wrt].shape == plain[wrt].shape
    assert _relative(got[wrt], plain[wrt]) < NEAR


def test_a_control_s_scan_stands_in_the_kernels_place(monkeypatch):
    """benchmark/tools/lm_granite_controls.py replaces ``ssd.scan`` by
    module attribute: ``scanned`` calls it, whatever the backend."""
    calls = []
    exact = ssd.scan

    def theirs(*args):
        calls.append(args[0].shape)
        return exact(*args)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ssd, "scan", theirs)
    y, _ = ssd.scanned(_Cfg, *_mixed(23))
    assert calls == [(2 * ssd.CHUNK, HEADS, LANES)]
    assert y.shape == (2 * ssd.CHUNK, HEADS * LANES)

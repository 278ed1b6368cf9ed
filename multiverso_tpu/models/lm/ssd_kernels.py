"""The state-space scan (ssd.py's ``scan``) as two Pallas kernels, forward
and backward, on a TPU at chunks of ``CHUNK`` = 256 positions, heads of 64
lanes and a state of 128 (``shapes_fit``; ssd.scan takes them there and
keeps its ``jax.numpy`` runs of chunks everywhere else, which is the
definition the tests hold these kernels to).

**What stays outside.** The log decay, its sums from a chunk's first
position (``G``, ``jnp.cumsum`` as the plain lines take it) and the deep
count are ``jax.numpy`` on [T, H] arrays of 2 MB, differentiated by JAX;
the kernels are ``_scan``: ``(X [T, H P], dt [T, H], G [T, H], B, C [T,
N]) -> Y [T, H P]`` under a ``custom_vjp``.

**Turned.** The kernels read every array with the positions along the
LANES: ``X^T`` [H P, T], ``dt^T`` and ``G^T`` [H, T], ``B^T`` and ``C^T`` [N,
T]. That is how the arrays lie in a layer program on a TPU (the compiler
lays a convolution's [T, channels] arrays out positions-minor, and the
whole mixer with it), so ``x.T`` at the kernel's door is no copy, where a
kernel over [T, H P] rows made the compiler turn X, Y and both cotangents
(134 MB each) around it. A head's 64 lanes are then 64 sublanes: a
position's scalar a head (``dt``, ``G`` and its exponentials) is a row
broadcast down them, a sum over a head's lanes a sum of registers, and no
[T, H, P] array is made.

**Forward** (``_forward``). Grid (chunk, head group), the chunk axis
outermost and in order, ``HEADS_A_STEP`` heads a grid step (their chains
wait on nothing of each other's): ALL heads' states are one scratch
[groups, a group's H P, N] float32, zeroed at the first chunk. ``(C B^T)^T``
[256, 256] is made once a chunk (at the first group) and kept in a scratch.
A step's two products with the state run over the whole group (``S C^T``:
[512, 128] x [128, 256]; ``(w dt X)^T B``: [512, 256] x [256, 128]); only
``(dt X)_h^T ((C B^T) * L_h)^T`` is a head's own, [64, 256] x [256, 256].
``L_h^T[s, t] = exp(G_t - G_s)`` with the mask put on the exponent before it
is taken: ``G_t`` along the lanes is the head's row of ``G^T``, ``G_s`` down
the sublanes one lane broadcast of its column of ``G`` [256, 128] (the
group's columns rolled to lane 0 first: the group is a grid index). With
``keep`` the state every chunk STARTED from goes out ([chunks, H P, N]
float32: 67 MB a sequence of 8,192, alive from this kernel to the next one
only).

**Backward** (``_backward``). The same grid walked from the last chunk to
the first, the state's cotangent the scratch; a chunk is made again from its
inputs and the state it started from. Written by hand (a trace of
``jax.vjp`` inside the kernel is what ``delta_kernels`` pays a second of
set-up for), with ``M = (C B^T) * L`` as [t, s] lies and ``dy`` rounded to
bfloat16 as ``bdot``'s rule does:

    d(dt X)_h^T = dy_h^T M_h + w (dS B^T)      dM_h = dy_h (dt X)_h^T
    d(C B^T) = sum_h dM_h * L_h                 (once a chunk -> dB, dC)
    dS_in = exp(G_last) dS + (exp(G) dy)^T C;   dB^T += dS^T (w dt X)^T,
    dC^T += S_in^T (exp(G) dy)^T                (over the whole group)
    dG_t = sum_s (dM * M)[t, s] - sum_t' (dM * M)[t', t]      exp's pull
           + exp(G_t) <dy_t, S C_t> - <w (dt X)_t, dS B_t>
           and at a chunk's last position + sum_s <w (dt X)_s, dS B_s>
           + exp(G_last) <dS, S_in>

``dM * M`` is summed in float32 as JAX's transpose sums it (rounding ``M``
first costs ``a_log``'s gradient a hundredth of its norm: the sums cancel).
Its sum over ``s`` runs along the lanes: a product with ones on the matrix
unit, float32 as two bfloat16 parts, which leaves a COLUMN (``d_cols``); the
other sums are sums of registers and leave rows of the turned cotangents.

**Precision** is ssd.py's: bfloat16 inputs and float32 sums where ``bdot``
stands there (``C B^T``, ``M (dt X)``, both products with the state, and
their cotangents rounded first), float32 for ``dt``, ``G``, every
exponential, ``L`` and the state from chunk to chunk. Every exponent is a
difference that is <= 0. ``ssd.CARRY`` and ``ssd.DECAY`` lower the
``jax.numpy`` path only: ``ssd.scan_in_kernels`` is false while either is
not float32.

Both ``pallas_call``s are named (``mv_ssd_scan_fwd``, ``mv_ssd_scan_bwd``)
and run under the caller's scope ``mv.lm.attn.ssd.scan`` with none of their
own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32, BF16 = jnp.float32, jnp.bfloat16
CHUNK, LANES, HEAD, STATE = 256, 128, 64, 128
#: The exponent of a pair that is not to count (its exponential is 0.0).
UNSEEN = -1e30
VMEM_LIMIT = 64 * 1024 * 1024
INTERPRET = False       # the tests' switch: the kernels run on the CPU
#: Heads a grid step takes where they divide the heads.
HEADS_A_STEP = 8


def shapes_fit(t: int, heads: int, lanes: int, state: int,
               chunk: int) -> bool:
    """The shapes the kernels take (ssd.scan_in_kernels adds the backend
    and the dtypes): whole chunks of 256, heads of 64 lanes by twos (their
    columns of ``G`` within one 128-lane tile), a state of 128."""
    return (chunk == CHUNK and t % CHUNK == 0 and lanes == HEAD
            and heads % 2 == 0 and heads <= LANES and state == STATE)


def _heads_a_step(heads: int) -> int:
    return next(per for per in (HEADS_A_STEP, 4, 2) if heads % per == 0)


# -- products, shapes of ones ----------------------------------------------------

def _dot(a, b, ca: int, cb: int):
    """``a``'s axis ``ca`` summed with ``b``'s ``cb``, float32 sums."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               preferred_element_type=F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _rolled(ref, by):
    """A [CHUNK, 128] block's lanes moved up by ``by`` (a grid index's
    multiple)."""
    return pltpu.roll(ref[...], by % LANES, 1)


def _down(columns, k: int):
    """Column ``k`` of ``columns`` [CHUNK, 128] along a chunk's lanes: a
    head's ``G`` down the sublanes."""
    return jnp.broadcast_to(columns[:, k:k + 1], (CHUNK, CHUNK))


def _over_lanes(z):
    """``z`` [rows, 256] summed over its lanes, every lane of the result
    [rows, 128] the sum. A product with ones, ``z`` as two bfloat16 parts
    (16 bits of it; float32 sums)."""
    z = z[:, :LANES] + z[:, LANES:]
    ones = jnp.ones((LANES, LANES), BF16)
    high = z.astype(BF16)
    low = (z - high.astype(F32)).astype(BF16)
    return _dot(high, ones, 1, 0) + _dot(low, ones, 1, 0)


def _all(z):
    """``z``'s sum [1, 1]."""
    return jnp.sum(jnp.sum(z, axis=0, keepdims=True), axis=1, keepdims=True)


class _Head:
    """What both walks make of a head's rows of a chunk, positions along
    the lanes: its ``dt`` and ``G`` [1, 256] (a row of the turned arrays),
    ``dt X`` (``xd`` [64, 256]), ``exp(G)``, ``w = exp(G_last - G)`` and
    ``exp(G_last)`` [1, 1]."""

    def __init__(self, dt_ref, rows_ref, head, x):
        self.dt = dt_ref[pl.ds(head, 1), :]
        self.g = rows_ref[pl.ds(head, 1), :]
        last = self.g[:, CHUNK - 1:]
        self.xd = self.dt * x
        self.grown = jnp.exp(self.g)
        self.w = jnp.exp(last - self.g)
        self.kept = jnp.exp(last)


def _a_head_s_rows(kept):
    """The heads' ``exp(G_last)`` [1, 1] down their rows of the state."""
    return jnp.concatenate(
        [jnp.broadcast_to(k, (HEAD, STATE)) for k in kept], axis=0)


# -- the kernels ------------------------------------------------------------------
# Every array lies TURNED, the positions along the lanes (the module's
# docstring).

def _forward_kernel(x_ref, bt_ref, ct_ref, dt_ref, rows_ref, cols_ref, y_ref,
                    *refs, per: int, keep: bool):
    if keep:
        kept_ref, state, cbt = refs
    else:
        state, cbt = refs
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        state[j] = jnp.zeros(state.shape[1:], F32)

    bt, ct = bt_ref[...].astype(BF16), ct_ref[...].astype(BF16)

    @pl.when(j == 0)
    def _():
        cbt[...] = _dot(bt, ct, 0, 0)                       # (C B^T)^T

    first = j * per
    cols = _rolled(cols_ref, -first)
    before = state[j]
    if keep:
        kept_ref[0] = before
    from_state = _dot(before.astype(BF16), ct, 1, 0)        # S C^T
    seen = _iota((CHUNK, CHUNK), 1) >= _iota((CHUNK, CHUNK), 0)
    added, kept = [], []
    for k in range(per):
        at = slice(k * HEAD, (k + 1) * HEAD)
        head = _Head(dt_ref, rows_ref, first + k, x_ref[at, :])
        # L^T [s, t] = exp(G_t - G_s), the mask on the exponent
        fall = jnp.exp(jnp.where(seen, head.g - _down(cols, k), UNSEEN))
        within = _dot(head.xd.astype(BF16),
                      (cbt[...] * fall).astype(BF16), 1, 0)
        y_ref[at, :] = within + head.grown * from_state[at, :]
        added.append((head.w * head.xd).astype(BF16))
        kept.append(head.kept)
    state[j] = _a_head_s_rows(kept) * before + _dot(
        jnp.concatenate(added, 0), bt, 1, 1)


def _backward_kernel(x_ref, bt_ref, ct_ref, dt_ref, rows_ref, cols_ref, dy_ref,
                     kept_ref, dx_ref, dbt, dct, ddt_ref, drows_ref, dcols_ref,
                     dstate, cb, dcb, *, per: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _():
        dstate[j] = jnp.zeros(dstate.shape[1:], F32)

    bt, ct = bt_ref[...].astype(BF16), ct_ref[...].astype(BF16)

    @pl.when(j == 0)
    def _():
        cb[...] = _dot(ct, bt, 0, 0)                        # C B^T [t, s]
        for ref in (dcb, dbt, dct, dcols_ref):
            ref[...] = jnp.zeros_like(ref)

    first = j * per
    cols = _rolled(cols_ref, -first)
    before, d_after = kept_ref[0], dstate[j]
    before_b, d_after_b = before.astype(BF16), d_after.astype(BF16)
    from_state = _dot(before_b, ct, 1, 0)                   # S C^T
    d_added = _dot(d_after_b, bt, 1, 0)                     # dS B^T
    seen = _iota((CHUNK, CHUNK), 0) >= _iota((CHUNK, CHUNK), 1)
    lane = _iota((1, LANES), 1)
    at_last = _iota((1, CHUNK), 1) == CHUNK - 1
    added, grown_dy, kept = [], [], []
    d_cols = jnp.zeros((CHUNK, LANES), F32)
    for k in range(per):
        at = slice(k * HEAD, (k + 1) * HEAD)
        x, dy = x_ref[at, :], dy_ref[at, :]
        head = _Head(dt_ref, rows_ref, first + k, x)
        xd, dy_b = head.xd.astype(BF16), dy.astype(BF16)
        # L [t, s] = exp(G_t - G_s), the mask on the exponent
        fall = jnp.exp(jnp.where(seen, _down(cols, k) - head.g, UNSEEN))
        m = cb[...] * fall
        d_m = _dot(dy_b, xd, 0, 0)                          # dy_h^T (dt X)_h
        dcb[...] += d_m * fall
        # exp's pull: + its rows' sums to G_t, - its columns' to G_s
        pulled = d_m * m
        d_cols = jnp.where(lane == k, _over_lanes(pulled), d_cols)
        added_here = head.w * head.xd
        d_xd = _dot(dy_b, m.astype(BF16), 1, 0) + head.w * d_added[at, :]
        dx_ref[at, :] = head.dt * d_xd
        ddt_ref[pl.ds(first + k, 1), :] = jnp.sum(d_xd * x, axis=0,
                                                  keepdims=True)
        of_added = jnp.sum(d_added[at, :] * added_here, axis=0,
                           keepdims=True)
        # what the chunk's last position gets of the state's two lines
        last = jnp.sum(of_added, axis=1, keepdims=True) \
            + head.kept * _all(d_after[at, :] * before[at, :])
        drows_ref[pl.ds(first + k, 1), :] = head.grown * jnp.sum(
            dy * from_state[at, :], axis=0, keepdims=True) - of_added \
            - jnp.sum(pulled, axis=0, keepdims=True) \
            + jnp.where(at_last, last, 0.0)
        added.append(added_here.astype(BF16))
        grown_dy.append((head.grown * dy).astype(BF16))
        kept.append(head.kept)
    added, grown_dy = jnp.concatenate(added, 0), jnp.concatenate(grown_dy, 0)
    dstate[j] = _a_head_s_rows(kept) * d_after + _dot(grown_dy, ct, 1, 1)
    dbt[...] += _dot(d_after_b, added, 0, 0)
    dct[...] += _dot(before_b, grown_dy, 0, 0)
    dcols_ref[...] += pltpu.roll(d_cols, first % LANES, 1)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        d_cb = dcb[...].astype(BF16)
        dbt[...] += _dot(ct, d_cb, 1, 0)
        dct[...] += _dot(bt, d_cb, 1, 1)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _switches():
    """What a kernel's trace reads beyond its arguments (the tests turn
    them): part of its jit's key."""
    return INTERPRET, BF16, HEADS_A_STEP


class _Specs:
    """The grid and the blocks of a sequence of ``t`` positions of ``heads``
    heads; the backward walk takes the chunks from the last."""

    def __init__(self, t: int, heads: int, backward: bool):
        n, per = t // CHUNK, _heads_a_step(heads)

        def chunk(i):
            return n - 1 - i if backward else i

        self.per, self.grid = per, (n, heads // per)
        #: a group's rows of a chunk of X^T, Y^T and their cotangents
        self.tile = pl.BlockSpec((per * HEAD, CHUNK),
                                 lambda i, j: (j, chunk(i)))
        #: a chunk of B^T, C^T and their cotangents
        self.turned = pl.BlockSpec((STATE, CHUNK),
                                   lambda i, j: (0, chunk(i)))
        #: every head's row of a chunk: dt^T, G^T and the cotangents' rows
        self.rows = pl.BlockSpec((heads, CHUNK), lambda i, j: (0, chunk(i)))
        #: a chunk's [256, 128] of G's columns
        self.cols = pl.BlockSpec((CHUNK, LANES), lambda i, j: (chunk(i), 0))
        self.kept = pl.BlockSpec((1, per * HEAD, STATE),
                                 lambda i, j: (chunk(i), j, 0))
        self.scratch = pltpu.VMEM((heads // per, per * HEAD, STATE), F32)


def _columns(sums):
    """``G`` [T, H] with its columns in one 128-lane tile."""
    return jnp.pad(sums, ((0, 0), (0, LANES - sums.shape[1])))


# Both walks are jitted for their TRACE, as delta_kernels' are: nine layers
# share a layer program, but the forward walk is traced in the forward
# program and again in the backward one.

@functools.partial(jax.jit, static_argnames=("keep", "switches"))
def _forward(x, dt, sums, b, c, *, keep: bool, switches):
    """x [T, H 64], dt, sums [T, H], b, c [T, 128] -> ``(y [T, H 64],)``
    and with ``keep`` the state every chunk started from [T / 256, H 64,
    128]."""
    t, heads = dt.shape
    of = _Specs(t, heads, False)
    out_shape = [jax.ShapeDtypeStruct(x.T.shape, F32)]
    out_specs = [of.tile]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct(
            (of.grid[0], x.shape[1], STATE), F32))
        out_specs.append(of.kept)
    y, *kept = pl.pallas_call(
        functools.partial(_forward_kernel, per=of.per, keep=keep),
        grid=of.grid,
        in_specs=[of.tile, of.turned, of.turned, of.rows, of.rows, of.cols],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[of.scratch, pltpu.VMEM((CHUNK, CHUNK), F32)],
        compiler_params=_params(), interpret=switches[0],
        name="mv_ssd_scan_fwd")(x.T, b.T, c.T, dt.T, sums.T, _columns(sums))
    return (y.T, *kept)


@functools.partial(jax.jit, static_argnames=("switches",))
def _backward(x, dt, sums, b, c, kept, dy, *, switches):
    """The pull of ``_forward``'s ``y``: ``(dx [T, H 64], d dt, d sums [T,
    H], db, dc [T, 128])``."""
    t, heads = dt.shape
    of = _Specs(t, heads, True)
    rows = jax.ShapeDtypeStruct((heads, t), F32)
    turned = jax.ShapeDtypeStruct((STATE, t), F32)
    dx, db, dc, d_dt, d_rows, d_cols = pl.pallas_call(
        functools.partial(_backward_kernel, per=of.per),
        grid=of.grid,
        in_specs=[of.tile, of.turned, of.turned, of.rows, of.rows, of.cols,
                  of.tile, of.kept],
        out_specs=[of.tile, of.turned, of.turned, of.rows, of.rows, of.cols],
        out_shape=[jax.ShapeDtypeStruct(x.T.shape, F32), turned, turned,
                   rows, rows, jax.ShapeDtypeStruct((t, LANES), F32)],
        scratch_shapes=[of.scratch, pltpu.VMEM((CHUNK, CHUNK), F32),
                        pltpu.VMEM((CHUNK, CHUNK), F32)],
        compiler_params=_params(), interpret=switches[0],
        name="mv_ssd_scan_bwd")(x.T, b.T, c.T, dt.T, sums.T, _columns(sums),
                                dy.T, kept)
    return dx.T, d_dt.T, d_rows.T + d_cols[:, :heads], db.T, dc.T


# -- ssd.scan's other path ----------------------------------------------------------

@jax.custom_vjp
def _scan(x, dt, sums, b, c):
    """``Y`` [T, H 64] of x [T, H 64], dt [T, H], the log decays summed from
    each chunk's first position [T, H], b, c [T, 128], all float32."""
    return _forward(x, dt, sums, b, c, keep=False, switches=_switches())[0]


def _scan_fwd(*args):
    y, kept = _forward(*args, keep=True, switches=_switches())
    return y, (*args, kept)


def _scan_bwd(res, dy):
    return _backward(*res, dy, switches=_switches())


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan(x, dt, g, b, c, deep: float):
    """ssd.scan at the shapes of ``shapes_fit``: x [T, H, 64], dt and the
    log decay ``g`` [T, H], b, c [T, 128] float32 -> ``(y [T, H, 64]
    float32, the count of (chunk, head) pairs whose summed log decay is
    under ``deep``)``."""
    t, heads, _ = x.shape
    sums = jnp.cumsum(g.reshape(t // CHUNK, CHUNK, heads), axis=1)
    y = _scan(x.reshape(t, -1), dt, sums.reshape(t, heads), b, c)
    return y.reshape(x.shape), jnp.sum(sums[:, -1] < deep, dtype=jnp.int32)

"""The delta rule's scan (delta.py's ``scan``) as two Pallas kernels, forward
and backward, on a TPU at chunks of ``CHUNK`` = 64 positions and heads of
128 lanes (``shapes_fit``; delta.scan takes them there and keeps its ``jax.numpy``
runs of chunks everywhere else, which is the definition the tests hold
these kernels to).

**Forward** (``_forward``). Grid (head, chunk), the chunk axis innermost and
in order: the state ``S`` [128 x 128] float32 is a scratch in fast memory,
zeroed at a head's first chunk and carried over its chunks. A grid step
reads the chunk's q, k, g, v tiles [64, 128] float32 straight from ``[T, H
K]`` (a free reshape of what ``gates`` returns: column block ``h`` is head
``h``) and beta's [64, H] rows, and ``_chunk`` makes everything of
delta.py's docstring there: ``G`` (a product with the lower triangle of
ones, float32 at "highest"), the decayed pairs ``A`` and ``B``, the solve,
``W``, ``U``, the four products with ``S``; it writes ``o``'s tile into ``[T,
H V]``, adds the chunk's deep channels to the head's count and, when the
backward pass will follow, ``KEPT`` a (head, chunk): the state the chunk
STARTED from ``[H, N, 128, 128]``, its ``M`` and its diagonal blocks (268 +
134 + 134 MB as stored for a sequence of 8192 tokens and 32 heads, alive
from this kernel to the next one only).

**The decayed pairs.** Sub-blocks of ``BLOCK`` = 16 positions as in
delta.py. A block of rows against the blocks before it: bfloat16 products
of ``x_t exp(G_t - G_n)`` and ``k_s exp(G_n - G_s)``, ``n`` the last
position before the row block, the three row blocks' products as ONE
product over a contraction three heads long. A block against itself,
channel by channel with the exponent masked before it is taken, by LAG: with
the channels down the sublanes and the positions along the lanes (k's and
q's rows side by side, one transpose each of [k; q], [k; k], [G; G]), the
pairs ``(t, t - d)`` of every position at once are one roll of the lanes by
``d``, and their sum over channels a sum of registers: 16 lags, no
reduction across lanes, no [16, 16, 128] array. The lags' rows are laid
into ``[s, t]`` by a select a lag and turned once.

**Backward** (``_backward``). The same grid walked from a head's last chunk
to its first, the state's cotangent the scratch. A grid step makes the
chunk again from its inputs and what the forward walk kept (``M`` and the
diagonal blocks are read, not made twice: ``_known_inverse``,
``_known_own_blocks``) and transposes it there: ``jax.vjp`` of ``_chunk``
INSIDE the kernel, so the transpose is the one JAX derives from the
forward's own lines (``bdot``'s rule: float32 sums of bfloat16 products, the
cotangent rounded first; the solve's pull ``-M^T g M^T`` at "highest"; the
blocks' walk transposed lag by lag; the cumulative sum's transpose the
product with the upper triangle), written as dq, dk, dg, dv tiles into the
natural layout and beta's as a row a (head, chunk).

**Precision** is delta.py's: bfloat16 inputs and float32 sums where ``bdot``
stands there, float32 for the log decay, its sums, every exponential, the
diagonal blocks, the solve ("highest"), the state. ``delta.CARRY`` lowers
the state of the ``jax.numpy`` path only: ``delta.scan_in_kernels`` is false
while it is not float32.

Both ``pallas_call``s are named (``mv_kda_scan_fwd``, ``mv_kda_scan_bwd``)
and run under the caller's scope ``mv.lm.attn.kda.scan`` with none of their
own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32, BF16 = jnp.float32, jnp.bfloat16
CHUNK, BLOCK, LANES = 64, 16, 128
HIGHEST = jax.lax.Precision.HIGHEST
#: The exponent of a pair that is not to count (its exponential is 0.0).
UNSEEN = -1e30
VMEM_LIMIT = 64 * 1024 * 1024
INTERPRET = False       # the tests' switch: the kernels run on the CPU
#: Heads a grid step takes where they divide the heads.
HEADS_A_STEP = 4
#: What the forward walk keeps a (head, chunk) for the backward one: the
#: state the chunk started from, its ``M``, its diagonal blocks.
KEPT = ((LANES, LANES), (CHUNK, CHUNK), (CHUNK, 2 * CHUNK))


def shapes_fit(t: int, k_lanes: int, v_lanes: int, chunk: int,
               block: int) -> bool:
    """The shapes the kernels take (delta.scan_in_kernels adds the backend
    and the state's dtype): whole chunks of 64 in sub-blocks of 16, heads
    of one 128-lane tile."""
    return (chunk == CHUNK and block == BLOCK and t % CHUNK == 0
            and k_lanes == LANES and v_lanes == LANES)


# -- products -------------------------------------------------------------------
# Every array below leads with the heads of one grid step, [P, rows, lanes]:
# one operation works on all of them, so that the heads' chains of small
# products, which wait on nothing of each other's, lie side by side in the
# program and the compiler overlaps them.

def _dot(a, b, ca: int, cb: int, precision=None):
    """``a``'s axis ``ca`` summed with ``b``'s ``cb`` (1 or 2), a head at a
    time."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((0,), (0,))),
                               precision=precision,
                               preferred_element_type=F32)


@functools.lru_cache(maxsize=None)
def _bdot(ca: int, cb: int, a_dtype, b_dtype):
    fa, fb = 3 - ca, 3 - cb

    def product(a, b):
        return _dot(a.astype(BF16), b.astype(BF16), ca, cb)

    def forward(a, b):
        return product(a, b), (a.astype(BF16), b.astype(BF16))

    def backward(res, g):
        a, b = res
        g = g.astype(BF16)
        da = _dot(g, b, 2, fb) if ca == 2 else _dot(b, g, fb, 2)
        db = _dot(g, a, 1, fa) if cb == 2 else _dot(a, g, fa, 1)
        return da.astype(a_dtype), db.astype(b_dtype)

    rule = jax.custom_vjp(product)
    rule.defvjp(forward, backward)
    return rule


def bdot(a, b, ca: int = 2, cb: int = 1):
    """delta.bdot for a matrix a head, ``a``'s axis ``ca`` summed with
    ``b``'s ``cb``: bfloat16 inputs, float32 sums, and cotangents that are
    float32 sums of bfloat16 inputs."""
    return _bdot(ca, cb, a.dtype, b.dtype)(a, b)


def _highest(a, b):
    return _dot(a, b, 2, 1, HIGHEST)


def _inverse(a):
    """delta.unit_lower_inverse: ``(I + a)^-1`` for ``a`` strictly lower, by
    halves (``M_2b = M_b - M_b D_b M_b``, ``D_b`` the lower-left quarters of
    ``a``'s diagonal blocks of ``2 b``)."""
    n = a.shape[-1]
    rows, cols = _iota((n, n), 0), _iota((n, n), 1)
    inverse, b = None, 1
    while b < n:
        quarter = (rows // (2 * b) == cols // (2 * b)) \
            & (rows // b % 2 == 1) & (cols // b % 2 == 0)
        part = jnp.where(quarter, a, 0.0)
        inverse = _eye(n) - part if inverse is None \
            else inverse - _highest(_highest(inverse, part), inverse)
        b *= 2
    return inverse


@jax.custom_vjp
def _known_inverse(a, m):
    """``_inverse(a)`` where the forward walk kept it: ``m``, with ``a``'s
    pull ``-M^T g M^T`` (delta.unit_lower_inverse's), the transposes taken
    by the products. Only the backward walk differentiates a chunk, and it
    always knows ``m``."""
    return m


def _known_inverse_fwd(a, m):
    return m, m


def _known_inverse_bwd(m, g):
    return (-_dot(_dot(m, g, 1, 1, HIGHEST), m, 2, 2, HIGHEST),
            jnp.zeros_like(m))


_known_inverse.defvjp(_known_inverse_fwd, _known_inverse_bwd)


# -- shapes of ones and zeros ----------------------------------------------------------

def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _eye(n):
    return (_iota((n, n), 0) == _iota((n, n), 1)).astype(F32)


def _turned(x):
    return jnp.swapaxes(x, 1, 2)


def _rolled(x, by: int):
    """``x``'s lanes moved up by ``by``: ``out[..., t] = x[..., t - by]``
    (and back down by a negative ``by``)."""
    by %= x.shape[2]
    return pltpu.roll(x, by, 2) if by else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _row(x, at: int):
    """Row ``at`` of a chunk's ``x`` [P, C, lanes] as [P, 1, lanes] (its
    pull a select, no pad)."""
    return x[:, at:at + 1, :]


def _row_fwd(x, at):
    return _row(x, at), None


def _row_bwd(at, _, g):
    return (jnp.where(_iota((CHUNK, g.shape[2]), 0) == at, g, 0.0),)


_row.defvjp(_row_fwd, _row_bwd)


@jax.custom_vjp
def _last_column(x):
    """The last column of a chunk's ``x`` [P, n, C] as [P, n, 1]."""
    return x[:, :, CHUNK - 1:]


def _last_column_fwd(x):
    return _last_column(x), None


def _last_column_bwd(_, g):
    return (jnp.where(_iota((g.shape[1], CHUNK), 1) == CHUNK - 1, g, 0.0),)


_last_column.defvjp(_last_column_fwd, _last_column_bwd)


# -- a chunk ---------------------------------------------------------------------------

# The diagonal blocks go by LAG, the channels down the sublanes and the
# positions along the lanes: the pairs (t, t - lag) of every position at once
# are one roll of the lanes, their sum over channels a sum of registers. A
# chunk has 64 positions and a register 128 lanes, so every array holds the
# chunk TWICE: ``*2`` a thing beside itself, ``*8`` a thing beside itself
# moved up by HALF = 8 positions, so that one roll by ``d`` makes lag ``d``
# in the lower lanes and lag ``d + 8`` in the upper (rolls are what this
# costs: the lane-rotate unit is the slowest the walk uses).
HALF = BLOCK // 2


def _halves(lower, upper):
    """The lower lanes of ``lower`` beside the upper lanes of ``upper``."""
    return jnp.where(_iota((1, 2 * CHUNK), 1) < CHUNK, lower, upper)


def _fall(g2, g8, d: int):
    """``exp(G_t - G_(t - lag))`` down the lanes, lag ``d`` | ``d + 8``; a
    pair outside its block decays to nothing (the exponent is masked
    before it is taken: exp(UNSEEN) is 0)."""
    lane = _iota((1, 2 * CHUNK), 1)
    seen = lane % BLOCK >= d + jnp.where(lane < CHUNK, 0, HALF)
    return jnp.exp(jnp.where(seen, g2 - _rolled(g8, d), UNSEEN))


def _apart():
    """``t - s`` over [s, t | t]."""
    c = CHUNK
    return _iota((c, 2 * c), 1) % c - _iota((c, 2 * c), 0)


def _own_blocks(k2, q2, g2, k8, g8):
    """The diagonal blocks of both decayed pairs, [P, K, 2 C] each (the
    note above) -> [P, C, 2 C], ``[s, t]`` of ``sum_c k_t k_s exp(G_t -
    G_s)`` beside the same of q_t, for ``s <= t`` of one block and zero
    elsewhere."""
    apart = _apart()
    turned = jnp.zeros((k2.shape[0], CHUNK, 2 * CHUNK), F32)
    for d in range(HALF):
        then = _rolled(k8, d) * _fall(g2, g8, d)
        of_a = jnp.sum(k2 * then, axis=1, keepdims=True)   # lag d | d + 8
        of_b = jnp.sum(q2 * then, axis=1, keepdims=True)
        turned = jnp.where(apart == d,
                           _halves(of_a, _rolled(of_b, CHUNK)), turned)
        turned = jnp.where(apart == d + HALF,
                           _halves(_rolled(of_a, CHUNK), of_b), turned)
    return turned


@jax.custom_vjp
def _known_own_blocks(k2, q2, g2, k8, g8, turned):
    """``_own_blocks`` where the forward walk kept it: ``turned``, with the
    pull of what it was computed from."""
    return turned


def _known_own_blocks_fwd(*args):
    return args[-1], args[:-1]


def _known_own_blocks_bwd(res, d_turned):
    """``_own_blocks``' walk transposed, a lag's decays made again where
    they are used: what a lag adds to ``k8`` and takes from ``g8`` is rolled
    back down, once (``k8`` is what ``k_then`` rolls back to)."""
    k2, q2, g2, k8, g8 = res
    apart = _apart()

    def of(lag):        # the lag's cotangents down the lanes [P, 1, tA | tB]
        return jnp.sum(jnp.where(apart == lag, d_turned, 0.0), axis=1,
                       keepdims=True)

    dk2, dq2, dg2, dk8 = (jnp.zeros_like(k2) for _ in range(4))
    for d in range(HALF):
        near, far = of(d), of(d + HALF)
        k_then, fall = _rolled(k8, d), _fall(g2, g8, d)
        of_a = _halves(near, _rolled(far, CHUNK)) * fall
        of_b = _halves(_rolled(near, CHUNK), far) * fall
        dk2, dq2 = dk2 + of_a * k_then, dq2 + of_b * k_then
        d_then = of_a * k2 + of_b * q2
        dg2 = dg2 + d_then * k_then
        dk8 = dk8 + _rolled(d_then, -d)
    return dk2, dq2, dg2, dk8, -dk8 * k8, jnp.zeros_like(d_turned)


_known_own_blocks.defvjp(_known_own_blocks_fwd, _known_own_blocks_bwd)


def _chunk(q, k, v, g, beta, state, known=None):
    """One chunk of a grid step's ``P`` heads, delta.py's ``_within`` and
    one step of its ``_across``: q, k, g [P, C, K], v [P, C, V], beta [P,
    C, 1], ``state`` [P, K, V], all float32 -> ``(o [P, C, V], the state
    after the chunk, (the chunk's summed log decay [P, 1, K], M = (I +
    A)^-1 [P, C, C], the diagonal blocks [P, C, 2 C]))``. ``known``: the
    last two where they are known (the backward walk reads what the forward
    one kept, and pulls through them without the solve's ten products or
    the blocks' first walk)."""
    c, heads = CHUNK, q.shape[0]
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    lower = jnp.broadcast_to((rows >= cols).astype(F32), (heads, c, c))
    G = _highest(lower, g)                           # summed log decays
    # the channels down the sublanes, every array the chunk twice (the note
    # above ``_own_blocks``): a thing beside itself, or beside itself eight
    # positions up (whole registers of rows: no data moves)
    def twice(a, up=0):
        upper = jnp.concatenate([jnp.zeros_like(a[:, :up]), a[:, :c - up]],
                                1) if up else a
        return _turned(jnp.concatenate([a, upper], 1))

    k2, g2 = twice(k), twice(G)
    stood = (k2, twice(q), g2, twice(k, HALF), twice(G, HALF))
    own = _own_blocks(*stood) if known is None \
        else _known_own_blocks(*stood, known[1])
    x2, G2 = jnp.concatenate([k, q], 1), jnp.concatenate([G, G], 1)

    # a block of rows against the blocks before it, by way of the position
    # before the block: one product, the three row blocks' side by side
    block_of = _iota((2 * c, 1), 0) % c // BLOCK
    place = _iota((c, 1), 0)
    before = jnp.zeros_like(G)
    for i in range(1, c // BLOCK):
        before = jnp.where(place // BLOCK == i, _row(G, i * BLOCK - 1),
                           before)
    left = x2 * jnp.exp(G2 - jnp.concatenate([before, before], 1))
    lefts, rights = [], []
    for i in range(1, c // BLOCK):
        at = _row(G, i * BLOCK - 1)
        early = place < i * BLOCK
        lefts.append(jnp.where(block_of == i, left, 0.0).astype(BF16))
        rights.append((k * jnp.exp(jnp.where(early, at - G, UNSEEN))
                       ).astype(BF16))
    pairs = _turned(own) + bdot(jnp.concatenate(lefts, 2), jnp.concatenate(rights, 2),
                       2, 2)
    a = beta * (rows > cols).astype(F32) * pairs[:, :c]
    b = pairs[:, c:]

    # M = I + X: the identity's part of both products is exact
    m = _inverse(a) if known is None else _known_inverse(a, known[0])
    x = m - _eye(c)
    kg = beta * k * jnp.exp(G)
    vb = beta * v
    w = kg + bdot(x, kg)
    u = vb + bdot(x, vb)

    # the state through the chunk
    r = u - bdot(w.astype(BF16), state)
    o = bdot((q * jnp.exp(G)).astype(BF16), state) + bdot(b.astype(BF16), r)
    kt, gt = k2[:, :, :c], g2[:, :, :c]              # [P, K, C]
    last = _last_column(gt)                          # [P, K, 1]
    state = jnp.exp(last) * state + bdot(
        (kt * jnp.exp(last - gt)).astype(BF16), r)
    return o, state, (_row(G, c - 1), m, own)


# -- the kernels ------------------------------------------------------------------------
# A grid step takes ``per`` heads' chunks, their lanes side by side in [T, H
# 128].

def _head_column(beta_ref, head):
    """Head ``head``'s column of beta's rows [C, H] as [C, 1]."""
    rows = beta_ref[...]
    return jnp.sum(jnp.where(_iota(rows.shape, 1) == head, rows, 0.0),
                   axis=1, keepdims=True)


def _lanes(j: int):
    return slice(j * LANES, (j + 1) * LANES)


def _tiles(ref, per: int):
    """A step's ``per`` heads' tiles [C, per 128] as [per, C, 128]."""
    return jnp.stack([ref[:, _lanes(j)] for j in range(per)])


def _columns(beta_ref, per: int):
    first = pl.program_id(0) * per
    return jnp.stack([_head_column(beta_ref, first + j) for j in range(per)])


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, *refs, deep: float,
                    keep: bool, per: int):
    if keep:
        o_ref, deep_ref, kept_ref, solved_ref, own_ref, state = refs
    else:
        o_ref, deep_ref, state = refs

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
        deep_ref[...] = jnp.zeros_like(deep_ref)

    before = state[...]
    o, after, (last, m, own) = _chunk(
        *(_tiles(ref, per) for ref in (q_ref, k_ref, v_ref, g_ref)),
        _columns(beta_ref, per), before)
    state[...] = after
    if keep:
        kept_ref[:, 0], solved_ref[:, 0], own_ref[:, 0] = before, m, own
    for j in range(per):
        o_ref[:, _lanes(j)] = o[j]
        deep_ref[0, :, _lanes(j)] += (last[j] < deep).astype(jnp.int32)


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, kept_ref,
                     solved_ref, own_ref, do_ref, dq_ref, dk_ref, dv_ref,
                     dg_ref, dbeta_ref, dstate, *, per: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    known = (solved_ref[:, 0], own_ref[:, 0])
    _, pull = jax.vjp(
        lambda *a: _chunk(*a, known=known)[:2],
        *(_tiles(ref, per) for ref in (q_ref, k_ref, v_ref, g_ref)),
        _columns(beta_ref, per), kept_ref[:, 0])
    dq, dk, dv, dg, dbeta, before = pull((_tiles(do_ref, per), dstate[...]))
    dstate[...] = before
    c = CHUNK
    for j in range(per):
        at = _lanes(j)
        dq_ref[:, at], dk_ref[:, at] = dq[j], dk[j]
        dv_ref[:, at], dg_ref[:, at] = dv[j], dg[j]
        # beta's column as the row this (head, chunk) writes
        dbeta_ref[0, j:j + 1, :] = jnp.sum(
            jnp.where(_iota((c, c), 0) == _iota((c, c), 1), dbeta[j], 0.0),
            axis=0, keepdims=True)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _heads_a_step(heads: int) -> int:
    return next(per for per in (HEADS_A_STEP, 2, 1) if heads % per == 0)


def _switches():
    """What a kernel's trace reads beyond its arguments (the tests turn
    both): part of its jit's key."""
    return INTERPRET, BF16


# Both walks are jitted for their TRACE: a kind of delta layer has a
# forward program (the scan) and a backward one (the scan made again, made
# again and kept, pulled), so the cell's two kinds trace a kernel eight
# times where three are distinct, and a trace of the backward kernel,
# ``jax.vjp`` of a chunk included, is a second and more of a busy host's
# time (``setup_s``). A jitted function called with the same shapes under
# another program's trace is not traced again.

@functools.partial(jax.jit, static_argnames=("deep", "keep", "switches"))
def _forward(q, k, v, g, beta, *, deep: float, keep: bool, switches):
    """q, k, g, v [T, H 128], beta [T, H] -> ``(o [T, H 128], the deep
    channels' count a head and lane [H / per, 1, per 128]``, and with
    ``keep`` the state every chunk started from [H, N, 128, 128], its ``M``
    [H, N, 64, 64] and its diagonal blocks [H, N, 64, 128]``)``."""
    t, heads = beta.shape
    n, per = t // CHUNK, _heads_a_step(heads)
    tile = pl.BlockSpec((CHUNK, per * LANES), lambda h, i: (i, h))
    out_shape = [jax.ShapeDtypeStruct((t, heads * LANES), F32),
                 jax.ShapeDtypeStruct((heads // per, 1, per * LANES),
                                      jnp.int32)]
    out_specs = [tile,
                 pl.BlockSpec((1, 1, per * LANES), lambda h, i: (h, 0, 0))]
    if keep:
        for square in KEPT:
            out_shape.append(jax.ShapeDtypeStruct((heads, n, *square), F32))
            out_specs.append(pl.BlockSpec((per, 1, *square),
                                          lambda h, i: (h, i, 0, 0)))
    return pl.pallas_call(
        functools.partial(_forward_kernel, deep=deep, keep=keep, per=per),
        grid=(heads // per, n),
        in_specs=[tile, tile, tile, tile,
                  pl.BlockSpec((CHUNK, heads), lambda h, i: (i, 0))],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((per, LANES, LANES), F32)],
        compiler_params=_params(), interpret=switches[0],
        name="mv_kda_scan_fwd")(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("switches",))
def _backward(q, k, v, g, beta, kept, do, *, switches):
    """The pull of ``_forward``'s ``o``: ``(dq, dk, dv, dg [T, H 128],
    dbeta [(H / per) N, per, 64])``, the chunks walked from the last to
    the first."""
    t, heads = beta.shape
    n, per = t // CHUNK, _heads_a_step(heads)
    tile = pl.BlockSpec((CHUNK, per * LANES), lambda h, i: (n - 1 - i, h))
    wide = jax.ShapeDtypeStruct((t, heads * LANES), F32)

    return pl.pallas_call(
        functools.partial(_backward_kernel, per=per),
        grid=(heads // per, n),
        in_specs=[tile, tile, tile, tile,
                  pl.BlockSpec((CHUNK, heads), lambda h, i: (n - 1 - i, 0)),
                  *(pl.BlockSpec((per, 1, *square),
                                 lambda h, i: (h, n - 1 - i, 0, 0))
                    for square in KEPT), tile],
        out_specs=[tile, tile, tile, tile,
                   pl.BlockSpec((1, per, CHUNK),
                                lambda h, i: (h * n + n - 1 - i, 0, 0))],
        out_shape=[wide, wide, wide, wide,
                   jax.ShapeDtypeStruct((heads // per * n, per, CHUNK), F32)],
        scratch_shapes=[pltpu.VMEM((per, LANES, LANES), F32)],
        compiler_params=_params(), interpret=switches[0],
        name="mv_kda_scan_bwd")(q, k, v, g, beta, *kept, do)


# -- delta.scan's other path -------------------------------------------------------------

def _flat(a):
    return a.reshape(a.shape[0], -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def scan(q, k, v, g, beta, deep: float):
    """delta.scan at the shapes of ``shapes_fit``: q, k, g, v [T, H, 128], beta
    [T, H] float32 -> ``(o [T, H, 128] float32, the count of (chunk, head,
    channel) triples whose summed log decay is under ``deep``)``."""
    o, deeps = _forward(*map(_flat, (q, k, v, g)), beta, deep=deep,
                        keep=False, switches=_switches())
    return o.reshape(v.shape), jnp.sum(deeps)


def _scan_fwd(q, k, v, g, beta, deep):
    o, deeps, *kept = _forward(*map(_flat, (q, k, v, g)), beta, deep=deep,
                               keep=True, switches=_switches())
    return (o.reshape(v.shape), jnp.sum(deeps)), (q, k, v, g, beta, kept)


def _scan_bwd(deep, res, cotangents):
    q, k, v, g, beta, kept = res
    do, _ = cotangents
    dq, dk, dv, dg, dbeta = _backward(*map(_flat, (q, k, v, g)), beta, kept,
                                      _flat(do), switches=_switches())
    t, heads = beta.shape
    per = dbeta.shape[1]
    # [H / per, N, per, C] -> [N, C, H / per, per]
    dbeta = dbeta.reshape(heads // per, t // CHUNK, per, CHUNK)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape),
            jnp.transpose(dbeta, (1, 3, 0, 2)).reshape(t, heads))


scan.defvjp(_scan_fwd, _scan_bwd)

"""The stream mixers' passes as Pallas kernels (TPU only; streams.py takes
them where ``jax.default_backend() == "tpu"`` and has the same sums in
``jax.numpy`` elsewhere). A stream tensor [n C, T] is read as [n, C, T]
and cut into blocks [n, ROWS, TOKENS]: every kernel sees all ``n`` streams
of a block at once, so a pass that needs them all reads each stream
tensor once. Per-token rows ([k, T]: coefficients, cotangents of the
coefficients) come in already spread over a tile's 8 sublanes ([k, 8,
T]), so that inside a kernel everything is elementwise on [8, TOKENS].

Every kernel is called under streams.py's scope ``mv.lm.hc`` and adds
none of its own: the benchmark reads the mixers' time by that name alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import streams

F32 = jnp.float32
ROWS, TOKENS, LANES, SUB = 256, 512, 128, 8
HIGHEST = jax.lax.Precision.HIGHEST
VMEM_LIMIT = 64 * 1024 * 1024


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def spread(rows):
    """[k, T] -> [k, 8, T]: a per-token row on every sublane of a tile."""
    return jnp.broadcast_to(rows[:, None, :], (rows.shape[0], SUB,
                                               rows.shape[1]))


# -- Sinkhorn: every round of one 128-token tile in one kernel ---------------------

def _nested(flat, n):
    return [list(flat[i * n:(i + 1) * n]) for i in range(n)]


def _flat(m):
    return tuple(e for line in m for e in line)


def _sinkhorn_kernel(z_ref, out_ref, *, n, iters, eps):
    def one(_, m):
        return _flat(streams.one_round(_nested(m, n), eps)[0])

    m = jax.lax.fori_loop(
        0, iters, one, tuple(jnp.exp(z_ref[k]) for k in range(n * n)))
    for k in range(n * n):
        out_ref[k] = m[k]


def _sinkhorn_pull_kernel(z_ref, g_ref, out_ref, lines_ref, inv_ref, *, n,
                          iters, eps):
    """The rounds made again, each half-round's lines and inverses kept
    in VMEM (``[round, half]``), then walked back."""
    first = tuple(jnp.exp(z_ref[k]) for k in range(n * n))

    def forth(r, m):
        m, kept = streams.one_round(_nested(m, n), eps)
        for half in range(2):
            for k, e in enumerate(_flat(kept[2 * half])):
                lines_ref[r, half, k] = e
            for i, e in enumerate(kept[2 * half + 1]):
                inv_ref[r, half, i] = e
        return _flat(m)

    jax.lax.fori_loop(0, iters, forth, first)

    def back(at, dm):
        r = iters - 1 - at
        kept = []
        for half in range(2):
            kept += [_nested([lines_ref[r, half, k] for k in range(n * n)],
                             n),
                     [inv_ref[r, half, i] for i in range(n)]]
        return _flat(streams.pull_round(kept, _nested(dm, n)))

    dm = jax.lax.fori_loop(0, iters, back,
                           tuple(g_ref[k] for k in range(n * n)))
    for k in range(n * n):
        out_ref[k] = dm[k] * first[k]


def _tiles(a):
    """[n, n, T] -> [n n, 8, T / 8]: an entry a run of whole tiles."""
    n, _, t = a.shape
    return a.reshape(n * n, SUB, t // SUB)


def _over_tiles(kernel, name, scratch, *entries):
    """``kernel`` over the 128-token tiles of ``entries`` [n, n, T]."""
    n, _, t = entries[0].shape
    spec = pl.BlockSpec((n * n, SUB, LANES), lambda i: (0, 0, i))
    out = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((n * n, SUB, t // SUB), F32),
        grid=(t // (SUB * LANES),), in_specs=[spec] * len(entries),
        out_specs=spec, scratch_shapes=scratch,
        compiler_params=_params("parallel"), name=name,
    )(*(_tiles(e) for e in entries))
    return out.reshape(entries[0].shape)


def sinkhorn(logits, iters: int, eps: float):
    n = logits.shape[0]
    return _over_tiles(
        functools.partial(_sinkhorn_kernel, n=n, iters=iters, eps=eps),
        "mv_hc_sinkhorn", [], logits)


def sinkhorn_pull(logits, g, iters: int, eps: float):
    n = logits.shape[0]
    return _over_tiles(
        functools.partial(_sinkhorn_pull_kernel, n=n, iters=iters, eps=eps),
        "mv_hc_sinkhorn_pull",
        [pltpu.VMEM((max(iters, 1), 2, n * n, SUB, LANES), F32),
         pltpu.VMEM((max(iters, 1), 2, n, SUB, LANES), F32)], logits, g)


# -- the passes over the streams ------------------------------------------------------
#
# A stream tensor comes as ``(stack [B, n, C, T], b)``: sequence ``b`` of a
# step's stack, read (or written) where it lies, so that no pass copies a
# sequence out of the stack or back into it. The ``b``s are prefetched
# scalars that the block index maps read.

def fits(tokens: int, rows=None) -> bool:
    """Whether the kernels take this size, whole tiles: Sinkhorn's two so
    many tokens, the passes over the streams also so many rows a
    stream."""
    return tokens % (SUB * LANES) == 0 and (rows is None or rows % ROWS == 0)


def _rows(r):
    return pl.ds(pl.multiple_of(r * SUB, SUB), SUB)


def _wide(n, which, order):
    """The block [n, ROWS, TOKENS] of sequence ``where[which]`` of a
    stack; ``order`` says which grid axis counts the rows' blocks."""
    def index(i, j, where):
        ci, ti = (i, j) if order == "ct" else (j, i)
        return where[which], 0, ci, ti
    return pl.BlockSpec((None, n, ROWS, TOKENS), index)


def _thin(order):
    def index(i, j, where):
        return (i, j) if order == "ct" else (j, i)
    return pl.BlockSpec((ROWS, TOKENS), index)


def _per_token(count, order):
    def index(i, j, where):
        return (0, 0, j if order == "ct" else i)
    return pl.BlockSpec((count, SUB, TOKENS), index)


def _call(kernel, name, grid, in_specs, out_specs, out_shape, where,
          scratch=(), aliases=None):
    """The kernel's call on its operands, ``where`` (the sequences of its
    stacks) prefetched before them."""
    call = pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=list(scratch)),
        compiler_params=_params("parallel", "arbitrary"), name=name,
        input_output_aliases=aliases or {})
    where = jnp.stack([jnp.asarray(b, jnp.int32) for b in where])
    return lambda *operands: call(where, *operands)


def _stats_kernel(where, phi_ref, x_ref, p_ref, ss_ref, *, n):
    """``phi X`` and the sum of squares of one block, added over the
    blocks of a token tile's column."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        p_ref[...] = jnp.zeros_like(p_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    p = p_ref[...]
    for j in range(n):
        p = p + jnp.dot(phi_ref[j], x_ref[j], precision=HIGHEST,
                        preferred_element_type=F32)
    p_ref[...] = p

    def chunk(r, ss):
        return ss + streams.total([x_ref[j, _rows(r), :] ** 2
                                   for j in range(n)])

    ss_ref[...] = jax.lax.fori_loop(0, ROWS // SUB, chunk, ss_ref[...])


def stats(phi, x):
    """``(phi X [k, T], the columns' sums of squares [8, T] by sublane)``
    in one read of ``x`` (a stack and its sequence)."""
    (stack, b), k = x, phi.shape[0]
    _, n, c, t = stack.shape
    call = _call(
        functools.partial(_stats_kernel, n=n), "mv_hc_stats",
        (t // TOKENS, c // ROWS),
        [pl.BlockSpec((n, k, ROWS), lambda ti, ci, where: (0, 0, ci)),
         _wide(n, 0, "tc")],
        (pl.BlockSpec((k, TOKENS), lambda ti, ci, where: (0, ti)),
         pl.BlockSpec((SUB, TOKENS), lambda ti, ci, where: (0, ti))),
        (jax.ShapeDtypeStruct((k, t), F32),
         jax.ShapeDtypeStruct((SUB, t), F32)), [b])
    return call(phi.reshape(k, n, c).transpose(1, 0, 2), stack)


def _write_kernel(where, x_ref, v_ref, res_ref, post_ref, *rest, n):
    y_ref = rest[-1]        # after the aliased stack, which is not read

    def chunk(r, _):
        at = _rows(r)
        xs = [x_ref[j, at, :] for j in range(n)]
        v = v_ref[at, :]
        for i in range(n):
            y_ref[i, at, :] = streams.total(
                [res_ref[i * n + j] * xs[j] for j in range(n)]) \
                + post_ref[i] * v
        return 0

    jax.lax.fori_loop(0, ROWS // SUB, chunk, 0)


def _into(into, like, which):
    """Where a pass writes: ``(the stack to write into or None, its
    sequence, the result's shape, the aliases)``: a new stack of one
    sequence, or ``into``'s (operand ``which``, the scalars counted),
    whose other sequences stay as they are."""
    if into is None:
        return None, 0, jax.ShapeDtypeStruct((1,) + like.shape[1:], F32), {}
    return into[0], into[1], jax.ShapeDtypeStruct(into[0].shape, F32), \
        {which: 0}


def write(x, v, res, post, into=None):
    """``X'_i = sum_j H_res[i, j] X_j + H_post[i] v`` from ``x`` (a stack
    and its sequence), ``v`` [C, T], ``res`` [n n, 8, T], ``post`` [n, 8,
    T]: a stack of one sequence, or ``into``'s (a stack and a sequence)
    with that sequence written."""
    stack, b = x
    _, n, c, t = stack.shape
    out, b_out, shape, aliases = _into(into, stack, 5)
    specs = [_wide(n, 0, "ct"), _thin("ct"), _per_token(n * n, "ct"),
             _per_token(n, "ct")]
    operands = [stack, v, res, post]
    if out is not None:
        specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(out)
    return _call(functools.partial(_write_kernel, n=n), "mv_hc_write",
                 (c // ROWS, t // TOKENS), specs, _wide(n, 1, "ct"), shape,
                 [b, b_out], aliases=aliases)(*operands)


def _weighted_kernel(where, x_ref, rows_ref, out_ref, *, n):
    def chunk(r, _):
        at = _rows(r)
        out_ref[at, :] = streams.total(
            [rows_ref[j] * x_ref[j, at, :] for j in range(n)])
        return 0

    jax.lax.fori_loop(0, ROWS // SUB, chunk, 0)


def weighted(x, rows):
    """``sum_j rows[j] X_j`` [C, T] of ``x`` (a stack and its sequence)
    by per-token ``rows`` [n, 8, T]."""
    stack, b = x
    _, n, c, t = stack.shape
    return _call(functools.partial(_weighted_kernel, n=n), "mv_hc_weighted",
                 (c // ROWS, t // TOKENS),
                 [_wide(n, 0, "ct"), _per_token(n, "ct")], _thin("ct"),
                 jax.ShapeDtypeStruct((c, t), F32), [b])(stack, rows)


def _sums_kernel(where, x_ref, dy_ref, v_ref, du_ref, out_ref, *, n):
    """The 2n + n^2 sums over a token's column of one block, a lane tile
    at a time so that the sums stay in registers; rows ``[d H_pre (n), d
    H_post (n), d H_res (n n)]``, each still spread over 8 sublanes."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    k = 2 * n + n * n
    for tile in range(TOKENS // LANES):
        lanes = pl.ds(tile * LANES, LANES)

        def chunk(r, sums):
            at = _rows(r)
            xs = [x_ref[j, at, lanes] for j in range(n)]
            dys = [dy_ref[i, at, lanes] for i in range(n)]
            v, du = v_ref[at, lanes], du_ref[at, lanes]
            new = [du * xj for xj in xs] + [dyi * v for dyi in dys] \
                + [dyi * xj for dyi in dys for xj in xs]
            return tuple(s + e for s, e in zip(sums, new))

        sums = jax.lax.fori_loop(
            0, ROWS // SUB, chunk,
            tuple(out_ref[row, :, lanes] for row in range(k)))
        for row in range(k):
            out_ref[row, :, lanes] = sums[row]


def sums(x, dy, v, du):
    """[2n + n^2, T]: ``sum_c du X_j``, ``sum_c dX'_i v``, ``sum_c dX'_i
    X_j`` in one read of ``x``, ``dy`` (each a stack and its sequence),
    ``v`` and ``du`` [C, T]."""
    (stack, b), (dy_stack, b_dy) = x, dy
    _, n, c, t = stack.shape
    k = 2 * n + n * n
    out = _call(
        functools.partial(_sums_kernel, n=n), "mv_hc_sums",
        (t // TOKENS, c // ROWS),
        [_wide(n, 0, "tc"), _wide(n, 1, "tc"), _thin("tc"), _thin("tc")],
        _per_token(k, "tc"), jax.ShapeDtypeStruct((k, SUB, t), F32),
        [b, b_dy])(stack, dy_stack, v, du)
    return jnp.sum(out, axis=1)


def _dx_kernel(where, x_ref, dy_ref, du_ref, phi_ref, dp_ref, dpt_ref,
               res_ref, pre_ref, shrink_ref, *rest, n):
    """``dX_j = sum_i H_res[i, j] dX'_i + H_pre[j] du + phi_j^T d - X_j
    shrink`` of one block, ``g = phi_j^T d`` made here (a product 2n + n^2
    deep) and never an array; and the block's part of ``d phi``, ``X_j
    d^T``, added over the token tiles."""
    dx_ref, dphi_ref, g_ref = rest[-3:]

    @pl.when(pl.program_id(1) == 0)
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)

    for j in range(n):
        g_ref[j] = jnp.dot(phi_ref[j], dp_ref[...], precision=HIGHEST,
                           preferred_element_type=F32)
        dphi_ref[j] += jnp.dot(x_ref[j], dpt_ref[...], precision=HIGHEST,
                               preferred_element_type=F32)

    def chunk(r, _):
        at = _rows(r)
        dys = [dy_ref[i, at, :] for i in range(n)]
        du = du_ref[at, :]
        for j in range(n):
            dx_ref[j, at, :] = streams.total(
                [res_ref[i * n + j] * dys[i] for i in range(n)]) \
                + pre_ref[j] * du + g_ref[j, at, :] \
                - x_ref[j, at, :] * shrink_ref[0]
        return 0

    jax.lax.fori_loop(0, ROWS // SUB, chunk, 0)


def dx(x, dy, du, phi, d_product, res, pre, shrink, into=None):
    """``(dX, d phi [k, n C])`` in one read of ``x``, ``dy`` (each a stack
    and its sequence) and ``du`` [C, T] and one write: ``d_product`` [k,
    T] is the cotangent of ``phi X``; ``res`` [n n, 8, T], ``pre`` [n, 8,
    T], ``shrink`` [1, 8, T] per-token rows spread over sublanes. ``dX``
    is a stack of one sequence, or ``into``'s with that sequence
    written."""
    (stack, b), (dy_stack, b_dy) = x, dy
    _, n, c, t = stack.shape
    k = phi.shape[0]
    out, b_out, shape, aliases = _into(into, stack, 10)
    tall = pl.BlockSpec((n, ROWS, k), lambda ci, ti, where: (0, ci, 0))
    specs = [_wide(n, 0, "ct"), _wide(n, 1, "ct"), _thin("ct"), tall,
             pl.BlockSpec((k, TOKENS), lambda ci, ti, where: (0, ti)),
             pl.BlockSpec((TOKENS, k), lambda ci, ti, where: (ti, 0)),
             _per_token(n * n, "ct"), _per_token(n, "ct"),
             _per_token(1, "ct")]
    operands = [stack, dy_stack, du, phi.T.reshape(n, c, k), d_product,
                d_product.T, res, pre, shrink]
    if out is not None:
        specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(out)
    dx_stack, d_phi = _call(
        functools.partial(_dx_kernel, n=n), "mv_hc_dx",
        (c // ROWS, t // TOKENS), specs, (_wide(n, 2, "ct"), tall),
        (shape, jax.ShapeDtypeStruct((n, c, k), F32)), [b, b_dy, b_out],
        scratch=[pltpu.VMEM((n, ROWS, TOKENS), F32)],
        aliases=aliases)(*operands)
    return dx_stack, d_phi.reshape(n * c, k).T

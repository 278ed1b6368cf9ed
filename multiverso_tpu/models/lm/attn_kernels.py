"""What stands between the attention's projections and its kernel, as one
pass over memory each way (TPU only; model.attention_inputs takes it where
``fits`` and has the same sums in ``jax.numpy`` elsewhere, which is the
definition the tests hold these kernels to).

Forward (``heads_in``): the float32 results of ``wq``, ``wk`` and ``wv``
come as the products leave them, [T, heads d], and are read once: a grid
step takes ``TOKENS`` rows of ONE key-value head's group (its ``per``
query heads' lanes, its key head's, its value head's) and, a head at a
time in float32, norms the head (``norm``), turns the first ``lanes`` lanes
(the rotary pairs' cos and sin come as [T, lanes / 2] and are widened to a
head's lanes once a block of tokens, by an exact product with ones), scales,
rounds, and writes the head where the attention kernel reads it ([groups,
per group, T, d]; [groups, T, d] for k and v). The turn of layout costs
nothing: a head's [TOKENS, d] tile is contiguous on both sides.

Backward (``_pull``): the same grid reads the kernel-layout cotangents and
(with ``norm`` alone) the products' results again, recomputes a head's
inverse norm, turns the rotary back (the rotation by the negative angle)
and writes the products' cotangents [T, heads d] in bfloat16, which is what
``model.mm``'s backward rule rounds them to first thing; the head norms'
scale gradients leave as one [8, d] partial sum a block of tokens, summed
outside. No float32 intermediate of the chain is written in either
direction.

Both kernels are called under model.py's scope ``mv.lm.attn.<kind>`` and
add none of their own.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
TOKENS, CHUNK, LANES, SUB = 512, 64, 128, 8
HIGHEST = jax.lax.Precision.HIGHEST
VMEM_LIMIT = 32 * 1024 * 1024
INTERPRET = False       # the tests' switch: the kernels run on the CPU


class Pass(NamedTuple):
    """One layer kind's pass: ``per`` query heads a key-value head, ``d``
    lanes a head of which the first ``lanes`` are turned (0: no rotary),
    ``norm`` whether the heads are normed (with ``eps``), ``scale`` what q
    is multiplied by, ``dtype`` what the attention kernel reads."""
    per: int
    d: int
    lanes: int
    norm: bool
    eps: float
    scale: float
    dtype: type


def fits(t: int, d: int) -> bool:
    """Whether the pass takes a sequence of ``t`` tokens with heads of
    ``d`` lanes: on a TPU, whole blocks of tokens, whole tiles of lanes."""
    return (jax.default_backend() == "tpu" and t % TOKENS == 0
            and d % LANES == 0)


def _widen(how: Pass, cos_ref, sin_ref, cos_w, sin_w, sign):
    """A block's cos and sin [TOKENS, lanes / 2] as the two tables a head's
    lanes are multiplied by: ``cos_w`` (cos on both halves of the turned
    lanes) beside the lane itself, ``sin_w`` beside its partner (minus sin
    on the first half, sin on the second; ``sign`` -1 turns back). A
    product with a matrix of ones and zeros at the highest precision, so
    every entry is the input's, exactly."""
    half = how.lanes // 2
    pair = jax.lax.broadcasted_iota(jnp.int32, (half, how.d), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (half, how.d), 1)
    first, second = lane == pair, lane == pair + half
    spread = functools.partial(jnp.dot, precision=HIGHEST,
                               preferred_element_type=F32)
    cos_w[...] = spread(cos_ref[...], (first | second).astype(F32))
    sin_w[...] = spread(sin_ref[...], sign * (second.astype(F32)
                                              - first.astype(F32)))


def _turned(how: Pass, y, cos, sin):
    """The rotary turn of ``y`` [rows, d] by the widened tables: a lane of
    the first half pairs with the one ``lanes / 2`` after it, a lane of
    the second with the one before; the lanes past ``lanes`` pass as they
    are, bit for bit."""
    half = how.lanes // 2
    if how.lanes == how.d:      # one roll brings both partners
        return y * cos + pltpu.roll(y, half, 1) * sin
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    partner = jnp.where(lane < half, pltpu.roll(y, how.d - half, 1),
                        pltpu.roll(y, half, 1))
    return jnp.where(lane < how.lanes, y * cos + partner * sin, y)


def _inverse_norm(how: Pass, x):
    return jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + how.eps)


def _chunks(body, init=0):
    def step(c, carry):
        return body(pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK), carry)
    return jax.lax.fori_loop(0, TOKENS // CHUNK, step, init)


def _split(how: Pass, refs, n_in: int):
    """The kernel's references by role: ``n_in`` leading tensors, then the
    two scales (``norm``), cos and sin (``lanes``), the outputs, and last
    the two widened tables (``lanes``)."""
    at = n_in
    lead, scales, tables = refs[:at], (None, None), (None, None)
    if how.norm:
        scales, at = refs[at:at + 2], at + 2
    if how.lanes:
        tables, at = refs[at:at + 2], at + 2
    wide = refs[-2:] if how.lanes else (None, None)
    outs = refs[at:len(refs) - 2] if how.lanes else refs[at:]
    return lead, scales, tables, outs, wide


def _in_kernel(*refs, how: Pass):
    (q_ref, k_ref, v_ref), scales, tables, (qo_ref, ko_ref, vo_ref), \
        (cos_w, sin_w) = _split(how, refs, 3)
    if how.lanes:
        @pl.when(pl.program_id(1) == 0)
        def _():
            _widen(how, *tables, cos_w, sin_w, 1.0)

    def head(x, scale_ref, rows, by):
        if how.norm:
            x = x * _inverse_norm(how, x) * scale_ref[...]
        if how.lanes:
            x = _turned(how, x, cos_w[rows, :], sin_w[rows, :])
        return (x if by == 1.0 else x * by).astype(qo_ref.dtype)

    def chunk(rows, carry):
        for j in range(how.per):
            qo_ref[j, rows, :] = head(
                q_ref[rows, j * how.d:(j + 1) * how.d], scales[0], rows,
                how.scale)
        ko_ref[rows, :] = head(k_ref[rows, :], scales[1], rows, 1.0)
        vo_ref[rows, :] = v_ref[rows, :].astype(vo_ref.dtype)
        return carry

    _chunks(chunk)


def _pull_kernel(*refs, how: Pass):
    lead, scales, tables, outs, (cos_w, sin_w) = _split(
        how, refs, 5 if how.norm else 3)
    dq_ref, dk_ref, dv_ref = lead[:3]
    dqf_ref, dkf_ref, dvf_ref = outs[:3]
    if how.lanes:
        @pl.when(pl.program_id(1) == 0)
        def _():
            _widen(how, *tables, cos_w, sin_w, -1.0)
    if how.norm:
        @pl.when(pl.program_id(1) == 0)
        def _():
            for ref in outs[3:]:
                ref[...] = jnp.zeros_like(ref)

    def head(g, x_ref, at, scale_ref, sum_ref, by):
        """A head's cotangent back through scale, turn and norm; ``at``
        is where the head's rows and lanes lie in the product's result."""
        g = g.astype(F32)
        if by != 1.0:
            g = g * by
        if how.lanes:
            g = _turned(how, g, cos_w[at[0], :], sin_w[at[0], :])
        if how.norm:
            # y = x r s, r = rsqrt(mean(x x) + eps): ds = sum g x r,
            # dx = r (u - x r r mean(u x)) with u = g s
            x = x_ref[at]
            r = _inverse_norm(how, x)
            normed = x * r
            sum_ref[...] += jnp.sum(
                (g * normed).reshape(CHUNK // SUB, SUB, how.d), axis=0)
            u = g * scale_ref[...]
            g = r * (u - normed * jnp.mean(u * normed, -1, keepdims=True))
        return g.astype(dqf_ref.dtype)

    (xq_ref, xk_ref), (sum_q, sum_k) = (lead[3:], outs[3:]) if how.norm \
        else ((None, None),) * 2

    def chunk(rows, carry):
        for j in range(how.per):
            at = rows, slice(j * how.d, (j + 1) * how.d)
            dqf_ref[at] = head(dq_ref[j, rows, :], xq_ref, at, scales[0],
                               sum_q, how.scale)
        at = rows, slice(None)
        dkf_ref[at] = head(dk_ref[at], xk_ref, at, scales[1], sum_k, 1.0)
        dvf_ref[at] = dv_ref[at].astype(dvf_ref.dtype)
        return carry

    _chunks(chunk)


def _specs(how: Pass):
    """The block of a group's lanes in a product's result, of its heads in
    the attention kernel's layout, and of the small operands."""
    d, per = how.d, how.per
    flat_q = pl.BlockSpec((TOKENS, per * d), lambda ti, g: (ti, g))
    flat = pl.BlockSpec((TOKENS, d), lambda ti, g: (ti, g))
    heads_q = pl.BlockSpec((None, per, TOKENS, d), lambda ti, g: (g, 0, ti, 0))
    heads = pl.BlockSpec((None, TOKENS, d), lambda ti, g: (g, ti, 0))
    small = []
    if how.norm:
        small += [pl.BlockSpec((1, d), lambda ti, g: (0, 0))] * 2
    if how.lanes:
        small += [pl.BlockSpec((TOKENS, how.lanes // 2),
                               lambda ti, g: (ti, 0))] * 2
    return (flat_q, flat, flat), (heads_q, heads, heads), small


def _call(kernel, how: Pass, name, t, groups, in_specs, out_specs, out_shape):
    return pl.pallas_call(
        functools.partial(kernel, how=how), out_shape=out_shape,
        grid=(t // TOKENS, groups), in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((TOKENS, how.d), F32)] * 2
        if how.lanes else [],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name=name, interpret=INTERPRET)


def _small(scales, tables):
    return [s.reshape(1, -1) for s in scales] + list(tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def heads_in(how: Pass, qf, kf, vf, scales, tables):
    """``(q [groups, per, T, d], k, v [groups, T, d])`` in ``how.dtype``
    from the three products' float32 results [T, heads d]: the heads
    normed by ``scales`` (the q and k norms' [d]; () without ``how.norm``),
    turned by ``tables`` (cos and sin [T, lanes / 2], float32; () without
    ``how.lanes``), q scaled."""
    t, groups = kf.shape[0], kf.shape[1] // how.d
    flat, heads, small = _specs(how)
    shapes = tuple(jax.ShapeDtypeStruct(s, how.dtype) for s in (
        (groups, how.per, t, how.d), (groups, t, how.d), (groups, t, how.d)))
    return _call(_in_kernel, how, "mv_attn_heads_in", t, groups,
                 list(flat) + small, heads, shapes)(
        qf, kf, vf, *_small(scales, tables))


def _heads_in_fwd(how, qf, kf, vf, scales, tables):
    kept = (qf, kf) if how.norm else ()
    return heads_in(how, qf, kf, vf, scales, tables), (kept, scales, tables)


def _pull(how: Pass, res, cotangents):
    kept, scales, tables = res
    dq, dk, dv = cotangents
    groups, t = dk.shape[:2]
    flat, heads, small = _specs(how)
    # in the dtype model.mm's backward rule rounds its cotangent to, first
    # thing: the kernel's own
    shapes = [jax.ShapeDtypeStruct((t, n * how.d), how.dtype)
              for n in (groups * how.per, groups, groups)]
    in_specs, out_specs = list(heads), list(flat)
    if how.norm:
        in_specs += flat[:2]
        partial_sum = pl.BlockSpec((None, SUB, how.d),
                                   lambda ti, g: (ti, 0, 0))
        out_specs += [partial_sum] * 2
        shapes += [jax.ShapeDtypeStruct((t // TOKENS, SUB, how.d), F32)] * 2
    out = _call(_pull_kernel, how, "mv_attn_heads_pull", t, groups,
                in_specs + small, out_specs, shapes)(
        dq, dk, dv, *kept, *_small(scales, tables))
    d_scales = tuple(s.sum((0, 1)) for s in out[3:])
    return (*(g.astype(F32) for g in out[:3]), d_scales,
            tuple(jnp.zeros_like(a) for a in tables))


heads_in.defvjp(_heads_in_fwd, _pull)

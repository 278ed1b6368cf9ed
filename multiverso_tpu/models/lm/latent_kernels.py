"""What stands between latent attention's products and its kernel, as one
pass over memory each way (TPU only; latent.inputs takes it where
``latent.pass_fused`` and has the same sums in ``jax.numpy`` elsewhere, which
is the definition the tests hold these kernels to). attn_kernels.py's pass
in the latent form: a head is ``[nope | rope]`` of which the LAST ``rope``
lanes are turned, its key's turned lanes are one ``k_r`` for all heads, and
``k_n`` and ``v`` leave one product side by side.

Forward (``heads_in``): the float32 result of ``W_qb`` [T, heads (nope +
rope)] comes as the product leaves it, with ``W_kva``'s last ``rope`` lanes
[T, rope]; ``W_kvb``'s [T, heads (nope + v)] comes ROUNDED (``k_n`` and
``v`` are only rounded on their way, and the compiler folds a rounding into
the product's own output fusion, as it did for the chain: half the bytes
written and read). Each is read once. A grid
step takes ``TOKENS`` rows of the fewest heads whose lanes make whole tiles
in both products (``together``: a head of 192 + 256 lanes starts mid-tile
every second time, so two). At a block's first step the rotary pairs' cos
and sin [T, rope / 2] are widened to the tiles that hold a head's last
``rope`` lanes (``wide`` lanes, by an exact product with ones) and ``k_r``
is turned, ONCE for all heads; then a head at a time, in float32, q's last
lanes are turned, q is scaled and rounded, and q [heads, 1, T, nope + rope],
k = [k_n | k_r] [heads, T, nope + rope] and v [heads, T, v] are written where
the attention kernel reads them.

Backward (``_pull``): the same grid reads the kernel-layout cotangents,
scales dq and turns its last lanes back (the rotation by the negative
angle), writes the two products' cotangents [T, heads (nope + rope)] and
[T, heads [dk_n | dv]] in bfloat16, which is what ``model.mm``'s backward
rule rounds them to first thing, and sums dk's last lanes over the heads
(the grid's second axis) into ``k_r``'s cotangent [T, rope], turned back
once, float32. There is no norm: cos and sin are all that is kept.

Both kernels are called under latent.py's scope ``mv.lm.attn.mla`` and add
none of their own.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attn_kernels import (F32, HIGHEST, LANES, TOKENS, VMEM_LIMIT,
                           _chunks)

INTERPRET = False       # the tests' switch: the kernels run on the CPU


class Pass(NamedTuple):
    """A latent layer's pass: a head's ``nope`` lanes as they are beside
    its ``rope`` turned ones, ``v`` lanes a value head, ``scale`` what q is
    multiplied by, ``dtype`` what the attention kernel reads."""
    nope: int
    rope: int
    v: int
    scale: float
    dtype: type

    @property
    def d(self) -> int:
        return self.nope + self.rope

    @property
    def wide(self) -> int:
        """The whole tiles at a head's end that hold its turned lanes."""
        return -(-self.rope // LANES) * LANES

    @property
    def together(self) -> int:
        """The fewest heads whose ``[k_n | v]`` make whole tiles."""
        return LANES // math.gcd(LANES, self.nope + self.v)


def fits(t: int, heads: int, how: Pass) -> bool:
    """Whether the kernels take a sequence of ``t`` tokens through
    ``heads`` heads: whole blocks of tokens, q's and v's heads whole tiles
    of lanes, and the heads whole steps of the grid."""
    return (t % TOKENS == 0 and how.rope > 0 and how.d % LANES == 0
            and how.v % LANES == 0 and heads % how.together == 0)


_spread = functools.partial(jnp.dot, precision=HIGHEST,
                            preferred_element_type=F32)


def _widen(how: Pass, cos_ref, sin_ref, cos_w, sin_w, sign):
    """A block's cos and sin [TOKENS, rope / 2] as the two tables a head's
    last ``wide`` lanes are multiplied by: ``cos_w`` (cos on both halves of
    the turned lanes) beside the lane itself, ``sin_w`` beside its partner
    (minus sin on the first half, sin on the second; ``sign`` -1 turns
    back). A product with a matrix of ones and zeros at the highest
    precision, so every entry is the input's, exactly."""
    half, first = how.rope // 2, how.wide - how.rope
    pair = jax.lax.broadcasted_iota(jnp.int32, (half, how.wide), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (half, how.wide), 1)
    one, other = lane == first + pair, lane == first + pair + half
    cos_w[...] = _spread(cos_ref[...], (one | other).astype(F32))
    sin_w[...] = _spread(sin_ref[...], sign * (other.astype(F32)
                                               - one.astype(F32)))


def _turned(how: Pass, y, cos, sin):
    """The rotary turn of the LAST ``rope`` lanes of ``y`` [rows, wide] by
    the widened tables: a lane of their first half pairs with the one
    ``rope / 2`` after it, a lane of the second with the one before; the
    lanes before them pass as they are, bit for bit."""
    half, first = how.rope // 2, how.wide - how.rope
    if not first:       # one roll brings both partners
        return y * cos + pltpu.roll(y, half, 1) * sin
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    partner = jnp.where(lane < first + half,
                        pltpu.roll(y, how.wide - half, 1),
                        pltpu.roll(y, half, 1))
    return jnp.where(lane >= first, y * cos + partner * sin, y)


def _in_kernel(q_ref, kv_ref, kr_ref, cos_ref, sin_ref, qo_ref, ko_ref,
               vo_ref, cos_w, sin_w, kr_w, *, how: Pass):
    d, wide, nope = how.d, how.wide, how.nope

    @pl.when(pl.program_id(1) == 0)
    def _():
        _widen(how, cos_ref, sin_ref, cos_w, sin_w, 1.0)
        # k_r where a head's last lanes lie, turned once for every head
        lane = jax.lax.broadcasted_iota(jnp.int32, (how.rope, wide), 1)
        at = jax.lax.broadcasted_iota(jnp.int32, (how.rope, wide), 0)
        place = (lane == wide - how.rope + at).astype(F32)

        def turn(rows, carry):
            kr_w[rows, :] = _turned(how, _spread(kr_ref[rows, :], place),
                                    cos_w[rows, :], sin_w[rows, :])
            return carry

        _chunks(turn)

    def chunk(rows, carry):
        cos, sin = cos_w[rows, :], sin_w[rows, :]
        k_r = kr_w[rows, wide - how.rope:].astype(ko_ref.dtype)
        for j in range(how.together):
            q = q_ref[rows, j * d:(j + 1) * d]
            if d > wide:
                qo_ref[j, rows, :d - wide] = (
                    q[:, :d - wide] * how.scale).astype(qo_ref.dtype)
            qo_ref[j, rows, d - wide:] = (
                _turned(how, q[:, d - wide:], cos, sin)
                * how.scale).astype(qo_ref.dtype)
            at = j * (nope + how.v)
            ko_ref[j, rows, :nope] = kv_ref[rows, at:at + nope]
            ko_ref[j, rows, nope:] = k_r
            vo_ref[j, rows, :] = kv_ref[rows, at + nope:at + nope + how.v]
        return carry

    _chunks(chunk)


def _pull_kernel(dq_ref, dk_ref, dv_ref, cos_ref, sin_ref, dqf_ref, dkvf_ref,
                 dkr_ref, cos_w, sin_w, sum_w, *, how: Pass):
    d, wide, nope = how.d, how.wide, how.nope

    @pl.when(pl.program_id(1) == 0)
    def _():
        _widen(how, cos_ref, sin_ref, cos_w, sin_w, -1.0)
        sum_w[...] = jnp.zeros_like(sum_w)

    def chunk(rows, carry):
        cos, sin = cos_w[rows, :], sin_w[rows, :]
        total = sum_w[rows, :]
        for j in range(how.together):
            g = dq_ref[j, rows, :].astype(F32) * how.scale
            if d > wide:
                dqf_ref[rows, j * d:(j + 1) * d - wide] = g[
                    :, :d - wide].astype(dqf_ref.dtype)
            dqf_ref[rows, (j + 1) * d - wide:(j + 1) * d] = _turned(
                how, g[:, d - wide:], cos, sin).astype(dqf_ref.dtype)
            at = j * (nope + how.v)
            dkvf_ref[rows, at:at + nope] = dk_ref[j, rows, :nope]
            dkvf_ref[rows, at + nope:at + nope + how.v] = dv_ref[j, rows, :]
            total = total + dk_ref[j, rows, d - wide:].astype(F32)
        sum_w[rows, :] = total
        return carry

    _chunks(chunk)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        def turn(rows, carry):
            back = _turned(how, sum_w[rows, :], cos_w[rows, :],
                           sin_w[rows, :])
            dkr_ref[rows, :] = back[:, wide - how.rope:]
            return carry

        _chunks(turn)


def _specs(how: Pass):
    """The block of a step's heads in a product's result and in the
    attention kernel's layout, and of the small operands."""
    n, d = how.together, how.d
    flat = [pl.BlockSpec((TOKENS, n * width), lambda ti, g: (ti, g))
            for width in (d, how.nope + how.v)]
    k_r = pl.BlockSpec((TOKENS, how.rope), lambda ti, g: (ti, 0))
    tables = [pl.BlockSpec((TOKENS, how.rope // 2),
                           lambda ti, g: (ti, 0))] * 2
    heads = [pl.BlockSpec((n, None, TOKENS, d), lambda ti, g: (g, 0, ti, 0)),
             pl.BlockSpec((n, TOKENS, d), lambda ti, g: (g, ti, 0)),
             pl.BlockSpec((n, TOKENS, how.v), lambda ti, g: (g, ti, 0))]
    return flat + [k_r], heads, tables


def _call(kernel, how: Pass, name, t, heads, in_specs, out_specs, out_shape):
    return pl.pallas_call(
        functools.partial(kernel, how=how), out_shape=out_shape,
        grid=(t // TOKENS, heads // how.together), in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((TOKENS, how.wide), F32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name=name, interpret=INTERPRET)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def heads_in(how: Pass, qf, kvf, k_r, tables):
    """``(q [heads, 1, T, nope + rope], k [heads, T, nope + rope], v
    [heads, T, v])`` in ``how.dtype`` from the float32 result of ``W_qb``
    [T, heads (nope + rope)], ``W_kvb``'s [T, heads (nope + v)] already in
    ``how.dtype`` and the shared key's float32 lanes ``k_r`` [T, rope]: q's
    and k's last ``rope`` lanes turned by ``tables`` (cos and sin [T, rope
    / 2], float32), q scaled."""
    t, heads = qf.shape[0], qf.shape[1] // how.d
    flat, laid, tables_specs = _specs(how)
    shapes = tuple(jax.ShapeDtypeStruct(s, how.dtype) for s in (
        (heads, 1, t, how.d), (heads, t, how.d), (heads, t, how.v)))
    return _call(_in_kernel, how, "mv_mla_heads_in", t, heads,
                 flat + tables_specs, laid, shapes)(qf, kvf, k_r, *tables)


def _heads_in_fwd(how, qf, kvf, k_r, tables):
    return heads_in(how, qf, kvf, k_r, tables), tables


def _pull(how: Pass, tables, cotangents):
    dq, dk, dv = cotangents
    heads, t = dk.shape[:2]
    flat, laid, tables_specs = _specs(how)
    # in the dtype model.mm's backward rule rounds its cotangent to, first
    # thing: the kernel's own (and ``kvf``'s); the shared key's sum stays
    # float32
    shapes = [jax.ShapeDtypeStruct((t, heads * how.d), how.dtype),
              jax.ShapeDtypeStruct((t, heads * (how.nope + how.v)),
                                   how.dtype),
              jax.ShapeDtypeStruct((t, how.rope), F32)]
    dqf, dkvf, dkr = _call(_pull_kernel, how, "mv_mla_heads_pull", t, heads,
                           laid + tables_specs, flat, shapes)(
        dq, dk, dv, *tables)
    return (dqf.astype(F32), dkvf, dkr,
            tuple(jnp.zeros_like(a) for a in tables))


heads_in.defvjp(_heads_in_fwd, _pull)

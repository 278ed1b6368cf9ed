"""What stands between a delta layer's projections, its scan and ``W_o``
as one pass over memory each way (TPU only; delta.py takes these where
``delta.passes_fused`` and has the same sums in ``jax.numpy`` elsewhere:
``delta.short_conv``, ``delta.gates`` and ``delta.output`` are the
definition the tests hold these kernels to).

``gates`` (kernel ``mv_kda_gates``): a grid step takes ``TOKENS`` rows of
``per`` heads' lanes of q, k (as the convolutions leave them) and the
decay's logits f, float32 [T, H 128] as they lie, and a head at a time in
float32 writes ``q / |q| * 128^-1/2``, ``k / |k|`` and ``g = -exp(a_log[h])
softplus(f + dt_bias)`` where delta_kernels' scan reads them ([T, H 128]);
beta's [TOKENS, H] block goes with a row block's first step. v is not
touched. ``conv_gates`` is the same kernel with q's and k's short
convolutions in it (``Pass.taps`` weights a channel, then silu: a row reads
the ``taps - 1`` rows before it, the block's first rows from the 8-row tile
before the block, which comes as a second view of the same array), for the
evaluations nothing is pulled through (two of a layer's three a sequence):
it reads the PRODUCTS' results, and q's and k's convolved copies are never
written. Its pull (``mv_kda_gates_pull``, of ``gates``) reads the three
cotangents and q, k, f again (the rule keeps its INPUTS alone), makes a
head's inverse norm and the softplus's slope again and writes dq, dk
(float32, the convolutions') and df, db in bfloat16, which is what
``model.mm``'s backward rule rounds them to first thing; ``d a_log`` and ``d
dt_bias`` leave as one [8, lanes] partial sum a block of tokens, summed
outside. ``conv`` (``mv_kda_conv``, at the file's end) is every other short
convolution: one read, one write; its pull (``mv_kda_conv_pull``) reads the
cotangent and the INPUT again (the tile AFTER a block too), writes dx in
bfloat16 and the weights' gradient as an [8, lanes] partial sum a weight.
``gated_norm`` (``mv_kda_out``): reads the scan's o and the gate's logits
once and writes ``rmsnorm_head(o) norm_o sigmoid(gate)`` in bfloat16, which
is what ``mm`` rounds ``W_o``'s input to. Its pull (``mv_kda_out_pull``)
reads ``W_o``'s cotangent, o and the gate again and writes do (float32, the
scan's), d gate (bfloat16, the projections') and ``d norm_o`` as partial
sums. A head's sum over its 128 lanes is a lane reduction a row
(``_head_sum``). As a product with a [128, 128] matrix of ones at the highest
precision on the otherwise idle matrix unit it was SLOWER on the chip in
three of the four kernels (PERF.md section 6, PR 64: 1.77 against 1.66 ms
for the gates' pull at 32 heads) and is not kept. No float32 intermediate of
either chain is written in either direction; the results differ from the
chain's by the order of a sum alone. The transposes are written out by hand.

The ``pallas_call``s run under the caller's scope (``mv.lm.attn.kda``, the
convolutions' ``.conv``) and add none of their own; their callers are jitted
for their TRACE, as delta_kernels' are.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32, BF16 = jnp.float32, jnp.bfloat16
TOKENS, ROWS, LANES, SUB = 512, 64, 128, 8
#: Heads a grid step takes where they divide the heads.
HEADS_A_STEP = 4
VMEM_LIMIT = 48 * 1024 * 1024
INTERPRET = False       # the tests' switch: the kernels run on the CPU


class Pass(NamedTuple):
    """One model's passes: ``heads`` held, beta's ``scale`` (1 or 2), the
    output norm's ``eps``, and the short convolutions' weights a channel
    where the gates' kernel convolves q and k itself (``taps``; 0: they come
    convolved)."""
    heads: int
    scale: float
    eps: float
    taps: int = 0

    @property
    def per(self) -> int:
        return next(p for p in (HEADS_A_STEP, 2, 1) if self.heads % p == 0)


def fits(t: int, d: int) -> bool:
    """The shapes the passes take (delta.passes_fused adds the backend):
    whole blocks of tokens, heads of one 128-lane tile."""
    return t % TOKENS == 0 and d == LANES


def _head_sum(x):
    """[rows, 128] -> [rows, 1]: a head's sum over its lanes."""
    return jnp.sum(x, -1, keepdims=True)


def _chunks(body):
    """``body(rows, c)`` for each run of ``ROWS`` rows of the block, ``c``
    its number."""
    def step(c, carry):
        body(pl.ds(pl.multiple_of(c * ROWS, ROWS), ROWS), c)
        return carry
    jax.lax.fori_loop(0, TOKENS // ROWS, step, 0)


def _heads(how: Pass):
    return [slice(j * LANES, (j + 1) * LANES) for j in range(how.per)]


def _folded(x):
    """[ROWS, 128] -> [8, 128]: the rows' sum as far as a tile."""
    return jnp.sum(x.reshape(ROWS // SUB, SUB, LANES), axis=0)


def _softplus(z):
    return jnp.maximum(z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z)))


# -- gates -------------------------------------------------------------------------

def _convolved(how: Pass, x_ref, before_ref, w_ref, rows, c, lanes, first):
    """delta.short_conv for a head's ``rows`` of the block in ``x_ref``:
    ``silu(sum_j w[j] x[t - (taps - 1) + j])``, the rows before the block's
    first from ``before_ref`` (the 8-row tile before the block; zeros
    before the sequence's first position: ``first``, whether the block is
    the sequence's first)."""
    x = x_ref[rows, lanes]
    tile = pl.ds(pl.multiple_of(jnp.maximum(c * ROWS - SUB, 0), SUB), SUB)
    before = jnp.where(c > 0, x_ref[tile, lanes],
                       jnp.where(first, 0.0, before_ref[:, lanes]))
    lagged = jnp.concatenate([before, x], axis=0)
    y = 0.0
    for j in range(how.taps):   # short_conv's order of summation
        lag = how.taps - 1 - j
        y = y + (pltpu.roll(lagged, lag, 0)[SUB:] if lag else x) \
            * w_ref[j:j + 1, lanes]
    return y * jax.nn.sigmoid(y)


def _gates_kernel(q_ref, k_ref, f_ref, b_ref, a_ref, bias_ref, *refs,
                  how: Pass):
    if how.taps:
        q_before, k_before, wq_ref, wk_ref, *refs = refs
    qo_ref, ko_ref, g_ref, beta_ref = refs

    @pl.when(pl.program_id(1) == 0)
    def _():
        beta = jax.nn.sigmoid(b_ref[...])
        beta_ref[...] = beta if how.scale == 1 else how.scale * beta

    first = pl.program_id(0) == 0

    def chunk(rows, c):
        for lanes in _heads(how):
            at = rows, lanes
            if how.taps:
                q = _convolved(how, q_ref, q_before, wq_ref, rows, c, lanes,
                               first)
                k = _convolved(how, k_ref, k_before, wk_ref, rows, c, lanes,
                               first)
            else:
                q, k = q_ref[at], k_ref[at]
            qo_ref[at] = q * (jax.lax.rsqrt(_head_sum(q * q))
                              * LANES ** -0.5)
            ko_ref[at] = k * jax.lax.rsqrt(_head_sum(k * k))
            g_ref[at] = a_ref[:, lanes] * _softplus(
                f_ref[at] + bias_ref[:, lanes])

    _chunks(chunk)


def _gates_pull_kernel(dq_ref, dk_ref, dg_ref, dbeta_ref, q_ref, k_ref, f_ref,
                       b_ref, a_ref, bias_ref, dqo_ref, dko_ref, df_ref,
                       db_ref, sum_a_ref, sum_bias_ref, *, how: Pass):
    @pl.when(pl.program_id(1) == 0)
    def _():
        # beta = scale s, s = sigmoid(b): db = dbeta scale s (1 - s)
        s = jax.nn.sigmoid(b_ref[...])
        db = dbeta_ref[...] * (s * (1.0 - s))
        db_ref[...] = (db if how.scale == 1 else how.scale * db).astype(
            db_ref.dtype)

    sum_a_ref[...] = jnp.zeros_like(sum_a_ref)
    sum_bias_ref[...] = jnp.zeros_like(sum_bias_ref)

    def unit_pull(x, g, by):
        # y = c x r, r = rsqrt(sum x x): dx = c r (g - x r r sum(g x))
        r = jax.lax.rsqrt(_head_sum(x * x))
        pulled = r * (g - x * (r * r * _head_sum(g * x)))
        return pulled if by == 1.0 else pulled * by

    def chunk(rows, c):
        for lanes in _heads(how):
            at = rows, lanes
            dqo_ref[at] = unit_pull(q_ref[at], dq_ref[at], LANES ** -0.5)
            dko_ref[at] = unit_pull(k_ref[at], dk_ref[at], 1.0)
            # g = a softplus(z), z = f + bias: dz = dg a sigmoid(z); a's own
            # cotangent is the sum of dg softplus(z) (times what a_log makes
            # of it, outside), the bias's the sum of dz
            z = f_ref[at] + bias_ref[:, lanes]
            dg = dg_ref[at]
            dz = dg * a_ref[:, lanes] * jax.nn.sigmoid(z)
            df_ref[at] = dz.astype(df_ref.dtype)
            sum_a_ref[:, lanes] += _folded(dg * _softplus(z))
            sum_bias_ref[:, lanes] += _folded(dz)

    _chunks(chunk)


def _gates_specs(how: Pass):
    wide = pl.BlockSpec((TOKENS, how.per * LANES), lambda ti, h: (ti, h))
    thin = pl.BlockSpec((TOKENS, how.heads), lambda ti, h: (ti, 0))
    lane = pl.BlockSpec((1, how.per * LANES), lambda ti, h: (0, h))
    sums = pl.BlockSpec((None, SUB, how.per * LANES),
                        lambda ti, h: (ti, 0, h))
    return wide, thin, lane, sums


def _conv_specs(how: Pass):
    """The 8-row tile before a block of tokens (the first block's is its
    own first tile, which the kernel does not read) and a convolution's
    weights [taps, lanes]."""
    tiles = TOKENS // SUB
    before = pl.BlockSpec((SUB, how.per * LANES),
                          lambda ti, h: (jnp.maximum(ti * tiles - 1, 0), h))
    return before, pl.BlockSpec((how.taps, how.per * LANES),
                                lambda ti, h: (0, h))


def _call(kernel, how: Pass, name, t, in_specs, out_specs, out_shape):
    return pl.pallas_call(
        functools.partial(kernel, how=how), out_shape=out_shape,
        grid=(t // TOKENS, how.heads // how.per), in_specs=in_specs,
        out_specs=out_specs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name=name, interpret=INTERPRET)


def _by_lane(how: Pass, a_log):
    """``-exp(a_log)`` a lane: [1, H 128]."""
    return jnp.repeat(-jnp.exp(a_log), LANES).reshape(1, how.heads * LANES)


@functools.partial(jax.jit, static_argnames=("how", "interpret"))
def _gates(how: Pass, convs, a_log, dt_bias, q, k, f, b, interpret):
    """``convs``: q's and k's convolutions' weights [H 128, taps] where
    ``how.taps``, else ()."""
    del interpret       # the jit's key: ``INTERPRET`` as the trace read it
    wide, thin, lane, _ = _gates_specs(how)
    before, weights = _conv_specs(how)
    like = jax.ShapeDtypeStruct(q.shape, F32)
    return _call(_gates_kernel, how, "mv_kda_gates", q.shape[0],
                 [wide, wide, wide, thin, lane, lane]
                 + [before, before, weights, weights] * bool(how.taps),
                 [wide, wide, wide, thin],
                 [like, like, like, jax.ShapeDtypeStruct(b.shape, F32)])(
        q, k, f, b, _by_lane(how, a_log), dt_bias.reshape(1, -1),
        *(q, k) * bool(how.taps), *(w.T for w in convs))


def conv_gates(how: Pass, conv_q, conv_k, a_log, dt_bias, q, k, f, b):
    """``gates`` of ``silu(conv(q))`` and ``silu(conv(k))`` for q, k as the
    PRODUCTS leave them and the two convolutions' weights [H 128, taps]:
    one kernel, forward only (nothing is pulled through it:
    ``delta.attention_vjp`` differentiates ``gates`` behind the
    convolutions' own pull)."""
    return tuple(_gates(how._replace(taps=conv_q.shape[1]), (conv_q, conv_k),
                        a_log, dt_bias, q, k, f, b, INTERPRET))


@functools.partial(jax.jit, static_argnames=("how", "interpret"))
def _gates_pull(how: Pass, a_log, dt_bias, q, k, f, b, cotangents,
                interpret):
    del interpret
    t, blocks = q.shape[0], q.shape[0] // TOKENS
    wide, thin, lane, sums = _gates_specs(how)
    like = jax.ShapeDtypeStruct(q.shape, F32)
    partial_sums = jax.ShapeDtypeStruct((blocks, SUB, q.shape[1]), F32)
    dq, dk, df, db, sum_a, sum_bias = _call(
        _gates_pull_kernel, how, "mv_kda_gates_pull", t,
        [wide, wide, wide, thin, wide, wide, wide, thin, lane, lane],
        [wide, wide, wide, thin, sums, sums],
        [like, like, jax.ShapeDtypeStruct(q.shape, BF16),
         jax.ShapeDtypeStruct(b.shape, BF16), partial_sums, partial_sums])(
        *cotangents, q, k, f, b, _by_lane(how, a_log),
        dt_bias.reshape(1, -1))
    d_a_log = -jnp.exp(a_log) * sum_a.sum((0, 1)).reshape(
        how.heads, LANES).sum(-1)
    return (d_a_log, sum_bias.sum((0, 1)), dq, dk, df.astype(F32),
            db.astype(F32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def gates(how: Pass, a_log, dt_bias, q, k, f, b):
    """``(q', k', g [T, H 128], beta [T, H])`` float32 as delta.scan's
    kernels read them, from the convolved q and k, the decay's logits f [T,
    H 128] and beta's b [T, H]: delta.gates without v, which passes."""
    return tuple(_gates(how, (), a_log, dt_bias, q, k, f, b, INTERPRET))


def _gates_fwd(how, *inputs):
    return gates(how, *inputs), inputs


def _gates_bwd(how, inputs, cotangents):
    return _gates_pull(how, *inputs, tuple(cotangents), INTERPRET)


gates.defvjp(_gates_fwd, _gates_bwd)


# -- the gated output norm --------------------------------------------------------------

def _inverse_rms(how: Pass, o):
    return jax.lax.rsqrt(_head_sum(o * o) * (1.0 / LANES) + how.eps)


def _out_kernel(o_ref, gate_ref, scale_ref, y_ref, *, how: Pass):
    def chunk(rows, c):
        for lanes in _heads(how):
            at = rows, lanes
            o = o_ref[at]
            y_ref[at] = (o * _inverse_rms(how, o) * scale_ref[...]
                         * jax.nn.sigmoid(gate_ref[at])).astype(y_ref.dtype)

    _chunks(chunk)


def _out_pull_kernel(dy_ref, o_ref, gate_ref, scale_ref, do_ref, dgate_ref,
                     sum_ref, *, how: Pass):
    @pl.when(pl.program_id(1) == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    def chunk(rows, c):
        for lanes in _heads(how):
            # y = n w s, n = o r, r = rsqrt(mean(o o) + eps), s = sigmoid(gate):
            # dw = sum dy n s, dgate = dy n w s (1 - s),
            # do = r (u - n mean(u n)) with u = dy w s
            at = rows, lanes
            o, dy = o_ref[at], dy_ref[at].astype(F32)
            r = _inverse_rms(how, o)
            normed = o * r
            s = jax.nn.sigmoid(gate_ref[at])
            passed = dy * s
            sum_ref[...] += _folded(passed * normed)
            u = passed * scale_ref[...]
            un = u * normed
            dgate_ref[at] = (un * (1.0 - s)).astype(dgate_ref.dtype)
            do_ref[at] = r * (u - normed * (_head_sum(un) * (1.0 / LANES)))

    _chunks(chunk)


def _out_specs(how: Pass):
    wide = pl.BlockSpec((TOKENS, how.per * LANES), lambda ti, h: (ti, h))
    return wide, pl.BlockSpec((1, LANES), lambda ti, h: (0, 0))


@functools.partial(jax.jit, static_argnames=("how", "interpret"))
def _gated_norm(how: Pass, norm_o, o, gate, interpret):
    del interpret
    wide, lane = _out_specs(how)
    return _call(_out_kernel, how, "mv_kda_out", o.shape[0],
                 [wide, wide, lane], wide,
                 jax.ShapeDtypeStruct(o.shape, BF16))(
        o, gate, norm_o.reshape(1, LANES))


@functools.partial(jax.jit, static_argnames=("how", "interpret"))
def _gated_norm_pull(how: Pass, norm_o, o, gate, dy, interpret):
    del interpret
    t = o.shape[0]
    wide, lane = _out_specs(how)
    do, d_gate, sums = _call(
        _out_pull_kernel, how, "mv_kda_out_pull", t,
        [wide, wide, wide, lane],
        [wide, wide, pl.BlockSpec((None, SUB, LANES),
                                  lambda ti, h: (ti, 0, 0))],
        [jax.ShapeDtypeStruct(o.shape, F32),
         jax.ShapeDtypeStruct(o.shape, BF16),
         jax.ShapeDtypeStruct((t // TOKENS, SUB, LANES), F32)])(
        dy, o, gate, norm_o.reshape(1, LANES))
    return sums.sum((0, 1)), do, d_gate.astype(F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def gated_norm(how: Pass, norm_o, o, gate):
    """``W_o``'s input [T, H 128] from the scan's o and the gate's logits
    (both [T, H 128] float32): each head's rms norm times ``norm_o`` [128]
    times the sigmoid of its gate, rounded to bfloat16 as ``model.mm`` would
    round it and handed on as float32 (the convert pair folds away, and
    ``W_o``'s cotangent comes back unrounded)."""
    return _gated_norm(how, norm_o, o, gate, INTERPRET).astype(F32)


def _gated_norm_fwd(how, *inputs):
    return gated_norm(how, *inputs), inputs


def _gated_norm_bwd(how, inputs, dy):
    return _gated_norm_pull(how, *inputs, dy, INTERPRET)


gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


# -- the short convolutions ------------------------------------------------------------

def _conv_kernel(x_ref, before_ref, w_ref, y_ref, *, how: Pass):
    first = pl.program_id(0) == 0

    def chunk(rows, c):
        for lanes in _heads(how):
            y_ref[rows, lanes] = _convolved(how, x_ref, before_ref, w_ref,
                                            rows, c, lanes, first)

    _chunks(chunk)


def _tile(start):
    return pl.ds(pl.multiple_of(start, SUB), SUB)


def _tile_before(ref, before_ref, c, lanes, first):
    """The 8 rows before chunk ``c`` of the block in ``ref``: the block's
    own, or before its first chunk ``before_ref``'s (zeros before the
    sequence's first position), as ``_convolved`` reads them."""
    return jnp.where(c > 0, ref[_tile(jnp.maximum(c * ROWS - SUB, 0)), lanes],
                     jnp.where(first, 0.0, before_ref[:, lanes]))


def _tile_after(ref, after_ref, c, lanes, last):
    """The 8 rows after chunk ``c``: the block's own, or after its last
    chunk ``after_ref``'s (zeros past the sequence's last position)."""
    return jnp.where(
        c < TOKENS // ROWS - 1,
        ref[_tile(jnp.minimum((c + 1) * ROWS, TOKENS - SUB)), lanes],
        jnp.where(last, 0.0, after_ref[:, lanes]))


def _conv_pull_kernel(g_ref, x_ref, g_after, x_before, x_after, w_ref, dx_ref,
                      dw_ref, *, how: Pass):
    first = pl.program_id(0) == 0
    last = pl.program_id(0) == pl.num_programs(0) - 1
    dw_ref[...] = jnp.zeros_like(dw_ref)
    leads = [how.taps - 1 - j for j in range(how.taps)]

    def chunk(rows, c):
        for lanes in _heads(how):
            # y = silu(u), u[t] = sum_j w[j] x[t - lead_j]: with gu = g
            # silu'(u), dx[t] = sum_j w[j] gu[t + lead_j] and dw[j] = sum_t
            # gu[t] x[t - lead_j]. u and gu are made for the chunk's rows
            # and the tile after them, which the chunk's last rows' dx reads
            lagged = jnp.concatenate([
                _tile_before(x_ref, x_before, c, lanes, first),
                x_ref[rows, lanes],
                _tile_after(x_ref, x_after, c, lanes, last)], 0)
            lags = [(pltpu.roll(lagged, lead, 0) if lead else lagged)[SUB:]
                    for lead in leads]
            u = 0.0
            for j, x in enumerate(lags):    # short_conv's order of summation
                u = u + x * w_ref[j:j + 1, lanes]
            s = jax.nn.sigmoid(u)
            g = jnp.concatenate([g_ref[rows, lanes], _tile_after(
                g_ref, g_after, c, lanes, last)], 0)
            gu = g * (s * (1.0 + u * (1.0 - s)))
            dx = 0.0
            for j, lead in enumerate(leads):
                ahead = pltpu.roll(gu, ROWS + SUB - lead, 0) if lead else gu
                dx = dx + ahead[:ROWS] * w_ref[j:j + 1, lanes]
                dw_ref[j, :, lanes] += _folded(gu[:ROWS] * lags[j][:ROWS])
            dx_ref[rows, lanes] = dx.astype(dx_ref.dtype)

    _chunks(chunk)


def _after_spec(how: Pass, t: int):
    """The 8-row tile after a block of tokens (the last block's is its own
    last tile, which the kernel does not read)."""
    tiles, end = TOKENS // SUB, t // SUB - 1
    return pl.BlockSpec(
        (SUB, how.per * LANES),
        lambda ti, h: (jnp.minimum((ti + 1) * tiles, end), h))


@functools.partial(jax.jit, static_argnames=("how", "interpret"))
def _conv(how: Pass, x, w, interpret):
    del interpret
    wide = _gates_specs(how)[0]
    return _call(_conv_kernel, how, "mv_kda_conv", x.shape[0],
                 [wide, *_conv_specs(how)], wide,
                 jax.ShapeDtypeStruct(x.shape, F32))(x, x, w.T)


@functools.partial(jax.jit, static_argnames=("how", "interpret"))
def _conv_pull(how: Pass, x, w, g, interpret):
    """``(dx, dw, the taps' partial sums [blocks, taps, 8, H 128])``."""
    del interpret
    t = x.shape[0]
    wide = _gates_specs(how)[0]
    before, weights = _conv_specs(how)
    after = _after_spec(how, t)
    dx, sums = _call(
        _conv_pull_kernel, how, "mv_kda_conv_pull", t,
        [wide, wide, after, before, after, weights],
        [wide, pl.BlockSpec((None, how.taps, SUB, how.per * LANES),
                            lambda ti, h: (ti, 0, 0, h))],
        [jax.ShapeDtypeStruct(x.shape, BF16), jax.ShapeDtypeStruct(
            (t // TOKENS, how.taps, SUB, x.shape[1]), F32)])(
        g, x, g, x, x, w.T)
    return dx.astype(F32), sums.sum((0, 2)).T, sums


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def conv(how: Pass, x, w):
    """delta.short_conv of x [T, H 128] float32 as a product leaves it
    under the weights w [H 128, taps]: one read and one write, and pulled
    (``mv_kda_conv_pull``) two reads and one."""
    return _conv(how._replace(taps=w.shape[1]), x, w, INTERPRET)


def _conv_fwd(how, *inputs):
    return conv(how, *inputs), inputs


def _conv_bwd(how, inputs, g):
    x, w = inputs
    return _conv_pull(how._replace(taps=w.shape[1]), x, w, g, INTERPRET)[:2]


conv.defvjp(_conv_fwd, _conv_bwd)

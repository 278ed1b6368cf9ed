"""The third family's residual path and its layer: ``hc_mult`` residual
streams mixed by matrices that Sinkhorn's iteration makes doubly
stochastic (manifold-constrained hyper-connections, arXiv 2512.24880, as
``model_type: xing4_0`` configures them: ``hc_mult``,
``hc_sinkhorn_iters``, ``hc_eps``, ``mhc_h_res_clamp_*``).

A layer's input is ``X`` [n C, T]: the ``n`` streams one under the other,
stream ``j`` the rows ``j C .. (j + 1) C - 1``, a COLUMN a token. That is
the layout the compiler gives the mixes whatever they are handed (two
dozen per-token coefficients broadcast over a stream go along sublanes
when the tokens lie along the lanes): handed [T, n C] it transposed every
stream tensor on its way in and out of every sublayer, a third of the
layer programs' time (PERF.md section 6, PR 39). (A [T, n, C] array would
be stored with its 4 rows padded to a tile's 8.) A sublayer ``F`` (the
latent attention, then the feed-forward) takes and gives [T, C], and has
its own ``phi`` [2n + n^2, n C] (a coefficient a row), ``b`` [2n + n^2]
and scalars ``a = (a_pre, a_post, a_res)``; per token

    r           = RMSNorm(X)                 over all n C, no weight
    [p, q, R]   = phi r                      [n | n | n x n], float32
    H_pre       = sigmoid(a_pre p + b_pre)                       [n]
    H_post      = 2 sigmoid(a_post q + b_post)                   [n]
    H_res       = SK(clamp(a_res R + b_res, clamp_min, clamp_max))
                  SK: exp, then ``hc_iters`` rounds of: rows over (their
                  sum + hc_eps), columns over (their sum + hc_eps)
    u           = sum_j H_pre[j] X_j                             ``read``
    v           = F(RMSNorm(u; g))
    X'_i        = sum_j H_res[i, j] X_j + H_post[i] v            ``write``

The embedding enters every stream alike (``expand``) and the streams are
summed before the final norm (``collapse``).

The feed-forward ``F`` is ``model.feed_forward_vjp``, which the plain
residual's layers call too: a dense MLP (``mv.lm.dense_mlp``) or, in a
sparse layer, the router (sigmoid scores, chosen through the bias:
``model.route``), the held routed experts (``model.routed_experts``) and
the shared expert (``mv.lm.shared_expert``), all on one normed ``h``.

``layer_vjp`` is the layer and what pulls a cotangent back through it;
the forward programs call it and drop the pull. Scope ``mv.lm.hc``: the
coefficients, Sinkhorn and the two mixes, forward and backward, and
nothing under it (the benchmark reads the mixers' time by that name
alone).

**The mixers' pull is written out** (``sublayer_vjp``; autodiff made some
twenty passes over a stream tensor where these are seven, PERF.md section
6, PR 40). With ``s = rsqrt(mean_c X^2 + eps)`` [1, T], ``raw = (phi X) s``
(``r = X s`` is NEVER an array: the norm's factor is a row, applied to the
[2n + n^2, T] product), ``v`` turned [C, T] and ``dX'`` the cotangent of
``X'``; a PASS reads or writes a whole [n C, T] float32 array once:

    forward   phi X and sum_c X^2            one pass over X   (``stats``)
              u = sum_j H_pre[j] X_j         one pass over X   (``read``)
              X' = H_res X + H_post v        X read, X' written (``write``)
    pull      dv = sum_i H_post[i] dX'_i     one pass over dX'
              du = F's pull of dv
              the 2n + n^2 sums over c       X and dX' read once each
                d H_pre[j] = sum_c du X_j;  d H_post[i] = sum_c dX'_i v;
                d H_res[i, j] = sum_c dX'_i X_j           (``_column_sums``)
              back through Sinkhorn's rounds, the clamp and the sigmoids on
                [2n + n^2, T] to d_raw, d b, d a    (``_pull_coefficients``)
              d = d_raw s (the cotangent of phi X);  m = (d_raw . raw) / (n C)
              dX_j = sum_i H_res[i, j] dX'_i + H_pre[j] du
                     + phi_j^T d - X_j s^2 m;  d phi = d X^T
                                X and dX' read, dX written (``_pull_streams``)

The norm's pull wants ``mean_c(g r)`` with ``g = phi^T d_raw``, and ``g . r
= d_raw . (phi r) = d_raw . raw``: a sum over 2n + n^2 rows, no pass; and
``g`` is a product 2n + n^2 deep made block by block inside the last pass,
never an array. In a backward program the forward is made again first
(the feed-forward sublayer's ``X'`` feeds nothing there and is not).
Sinkhorn (``sinkhorn``, with a rule of its own that the module-level name
is looked up for, so what replaces it is pulled by ITS rule) sums ``n``
slices by adds and divides by multiplying with a line's inverse: no
``reduce``; its pull makes the rounds again, keeps each half-round's lines
and inverses, and walks back through every one exactly.

On a TPU each pass is one Pallas kernel (streams_kernels.py; off it, and
at sizes that are not whole tiles, the same sums in ``jax.numpy``, chosen
by ``jax.default_backend()`` as ``_apart`` is), and a sequence is read and
written where it lies in its step's stack (``Of``): the layer programs'
loop over sequences copies no sequence out of the stack or back into it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import latent
from . import model as lm
from .model import F32, LMConfig

SCOPE = "mv.lm.hc"
SUBLAYERS = ("hc_attn", "hc_ffn")
MIXER = ("phi", "b", "a")


def total(parts):
    """The parts added one to the next: a sum the compiler sees as
    elementwise adds, not as a ``reduce`` that ends a fusion."""
    summed = parts[0]
    for part in parts[1:]:
        summed = summed + part
    return summed


def over_line_sums(m, eps):
    """Each line (inner list) of ``m`` over (its sum + eps): ``(the
    lines, each line's 1 / (sum + eps))``."""
    inv = [1.0 / (total(line) + eps) for line in m]
    return [[e * r for e in line] for line, r in zip(m, inv)], inv


def pull_line_sums(y, inv, dy):
    """``over_line_sums``' pull from its results: ``y_j = m_j / (sum +
    eps)`` gives ``dm_j = (dy_j - sum_k dy_k y_k) / (sum + eps)``."""
    out = []
    for y_line, r, dy_line in zip(y, inv, dy):
        dot = total([d * e for d, e in zip(dy_line, y_line)])
        out.append([(d - dot) * r for d in dy_line])
    return out


def turned(m):
    return [list(line) for line in zip(*m)]


def one_round(m, eps):
    """One Sinkhorn round on the entries ``m`` (n rows of n): rows over
    their sums, then columns over theirs: ``(the entries after it, what
    its pull needs: the rows' half as it lies, the columns' turned, each
    with its lines' inverses)``."""
    rows, inv_rows = over_line_sums(m, eps)
    columns, inv_columns = over_line_sums(turned(rows), eps)
    return turned(columns), (rows, inv_rows, columns, inv_columns)


def pull_round(kept, dm):
    """``one_round``'s pull: the cotangent of the entries after the round
    -> that of the entries before it."""
    rows, inv_rows, columns, inv_columns = kept
    dm = turned(pull_line_sums(columns, inv_columns, turned(dm)))
    return pull_line_sums(rows, inv_rows, dm)


def _entries(m):
    """[n, n, T] -> its n x n entries [T], a list a row."""
    return [[m[i, j] for j in range(m.shape[1])] for i in range(m.shape[0])]


def _stacked(m):
    return jnp.stack([jnp.stack(row) for row in m])


def _round_of_arrays(m, eps):
    """``one_round`` on [n, n, T]: the loop over rounds carries arrays."""
    after, (rows, inv_rows, columns, inv_columns) = one_round(_entries(m),
                                                              eps)
    return _stacked(after), (_stacked(rows), jnp.stack(inv_rows),
                             _stacked(columns), jnp.stack(inv_columns))


def _kernels(tokens, rows=None):
    """streams_kernels where its kernels run and take this size (so many
    tokens; for the passes over the streams, so many ``rows`` a stream),
    else None: on a TPU, whole tiles. Off the TPU (tests, rehearsals)
    and at odd sizes the same sums are ``jax.numpy``'s."""
    if jax.default_backend() != "tpu":
        return None
    from . import streams_kernels
    return streams_kernels if streams_kernels.fits(tokens, rows) else None


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def sinkhorn(logits, iters: int, eps: float):
    """[n, n, T] (row, column, token) -> the same made (nearly) doubly
    stochastic a token: ``exp``, then ``iters`` rounds of row, then
    column normalisation. Every sum is over ``n`` slices added one to
    the next and every quotient a product with a line's inverse, so a
    round is elementwise on [T] slices and holds no ``reduce`` (on a TPU
    one kernel makes all the rounds, a tile of tokens at a time); the
    pull goes back through every round exactly, from the rounds made
    again."""
    kernels = _kernels(logits.shape[-1])
    if kernels is not None:
        return kernels.sinkhorn(logits, iters, eps)
    return jax.lax.fori_loop(
        0, iters, lambda _, m: _round_of_arrays(m, eps)[0], jnp.exp(logits))


def _sinkhorn_fwd(logits, iters, eps):
    return sinkhorn(logits, iters, eps), logits


def _sinkhorn_bwd(iters, eps, logits, g):
    kernels = _kernels(logits.shape[-1])
    if kernels is not None:
        return (kernels.sinkhorn_pull(logits, g, iters, eps),)
    first = jnp.exp(logits)
    _, kept = jax.lax.scan(lambda m, _: _round_of_arrays(m, eps), first,
                           None, length=iters)

    def back(dm, kept):
        rows, inv_rows, columns, inv_columns = kept
        return _stacked(pull_round(
            (_entries(rows), list(inv_rows), _entries(columns),
             list(inv_columns)), _entries(dm))), None

    dm, _ = jax.lax.scan(back, g, kept, reverse=True)
    return (dm * first,)


sinkhorn.defvjp(_sinkhorn_fwd, _sinkhorn_bwd)


class Of(NamedTuple):
    """Sequence ``b`` of a step's ``stack`` [B, n C, T], where a layer
    may be handed its streams (and told to leave its result): on a TPU
    the passes read and write the sequence where it lies, and nothing
    copies it out of the stack or back."""
    stack: jax.Array
    b: jax.Array | int


def _whole(x):
    """The streams [n C, T] themselves."""
    if not isinstance(x, Of):
        return x
    return jax.lax.dynamic_index_in_dim(x.stack, x.b, 0, keepdims=False)


def _placed(y, into):
    """``y`` [n C, T] as a layer returns it: itself, or ``into``'s stack
    with it as that sequence."""
    if into is None:
        return y
    return jax.lax.dynamic_update_index_in_dim(into.stack, y, into.b, 0)


def _tokens(x):
    return (x.stack if isinstance(x, Of) else x).shape[-1]


def _streams(cfg: LMConfig, x):
    """The n streams [C, T] of ``x``; of an ``Of`` each cut straight from
    the stack, so that it fuses into what reads it."""
    c = cfg.hidden
    if not isinstance(x, Of):
        return [x[j * c:(j + 1) * c] for j in range(cfg.hc_mult)]
    return [jax.lax.dynamic_slice(
        x.stack, (x.b, j * c, 0), (1, c, x.stack.shape[-1]))[0]
        for j in range(cfg.hc_mult)]


def _operand(cfg: LMConfig, x):
    """``(stack [B, n, C, T], sequence)`` as the kernels read streams."""
    stack, b = x if isinstance(x, Of) else (x[None], 0)
    return stack.reshape(stack.shape[0], cfg.hc_mult, cfg.hidden, -1), b


def _result(x, into):
    """A kernel's stack as a layer returns it: the one sequence [n C, T],
    or ``into``'s stack whole."""
    return x.reshape((-1,) + x.shape[-1:]) if into is None \
        else x.reshape(into.stack.shape)


def _product_and_scale(cfg: LMConfig, phi, x):
    """``(phi X [2n + n^2, T], s [1, T])``, ``s = rsqrt(mean over all n C
    of X^2 + eps)``: RMSNorm's factor, which is all of ``r = X s`` that
    is ever made."""
    kernels = _kernels(_tokens(x), cfg.hidden)
    if kernels is not None:
        product, squares = kernels.stats(phi, _operand(cfg, x))
        squares = jnp.sum(squares, 0, keepdims=True)
    else:
        product = jnp.dot(phi, _whole(x), precision="highest")
        squares = total([jnp.sum(xj * xj, 0, keepdims=True)
                         for xj in _streams(cfg, x)])
    return product, jax.lax.rsqrt(
        squares / (cfg.hc_mult * cfg.hidden) + cfg.eps)


def _logits(cfg: LMConfig, hc, raw):
    """``a raw + b`` by group: ``(pre [n, T], post [n, T], res [n^2, T])``
    before the sigmoids and the clamp."""
    n = cfg.hc_mult
    b, a = hc["b"][:, None], hc["a"]
    return (a[0] * raw[:n] + b[:n], a[1] * raw[n:2 * n] + b[n:2 * n],
            a[2] * raw[2 * n:] + b[2 * n:])


def coefficients(cfg: LMConfig, hc, x):
    """The coefficients and what their pull needs: ``((H_pre, H_post,
    H_res), (s, raw, the clamp's mask, Sinkhorn's pull))``. ``raw = (phi
    X) s``: the norm's factor is a [1, T] row applied to a [2n + n^2, T]
    product, so ``r = X s`` is never an array."""
    n = cfg.hc_mult
    product, s = _product_and_scale(cfg, hc["phi"], x)
    raw = product * s
    pre, post, res = _logits(cfg, hc, raw)
    low, high = cfg.hc_clamp
    inside = (res >= low) & (res <= high)
    res, pull_res = jax.vjp(
        lambda z: sinkhorn(z, cfg.hc_iters, cfg.hc_eps),
        jnp.clip(res, low, high).reshape(n, n, -1))
    return ((jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), res),
            (s, raw, inside, pull_res))


def _apart(v):
    if jax.default_backend() == "tpu":
        return v.T
    # XLA folds a product's transpose into the product whatever stands
    # between them, and its CPU runtime lacks the bfloat16 product that
    # makes (both operands transposed, DotThunk): off the TPU the
    # transpose is a float32 product with the identity, which is exact
    # and which nothing is folded into. Tests and rehearsals only.
    return jax.lax.dot_general(v, jnp.eye(v.shape[0], dtype=v.dtype),
                               (((0,), (0,)), ((), ())), precision="highest")


def read(cfg: LMConfig, x, pre):
    """A sublayer's input ``u`` [C, T] of the streams ``x``: ``sum_j
    H_pre[j] X_j``."""
    return total([pre[j:j + 1] * xj
                  for j, xj in enumerate(_streams(cfg, x))])


def _pull_write_to_v(cfg: LMConfig, dy, post):
    """``dv = sum_i H_post[i] dX'_i`` [C, T]. (A kernel on a TPU, where
    the compiler, asked for ``dv`` turned, turns every ``dX'_i`` first.)"""
    kernels = _kernels(_tokens(dy), cfg.hidden)
    if kernels is not None:
        return kernels.weighted(_operand(cfg, dy), kernels.spread(post))
    return total([post[i:i + 1] * dyi
                  for i, dyi in enumerate(_streams(cfg, dy))])


def write(cfg: LMConfig, x, post, res, v, into=None):
    """The streams after a sublayer whose ``F`` gave ``v`` [C, T]: [n C,
    T], or ``into``'s stack with them as that sequence."""
    n = cfg.hc_mult
    kernels = _kernels(_tokens(x), cfg.hidden)
    if kernels is not None:
        return _result(kernels.write(
            _operand(cfg, x), v, kernels.spread(res.reshape(n * n, -1)),
            kernels.spread(post), into and _operand(cfg, into)), into)
    xs = _streams(cfg, x)
    return _placed(jnp.concatenate([
        total([res[i, j][None] * xj for j, xj in enumerate(xs)])
        + post[i:i + 1] * v for i in range(n)], axis=0), into)


def expand(cfg: LMConfig, h):
    """[.., T, C] -> [.., n C, T]: every stream starts as ``h``."""
    return jnp.tile(jnp.swapaxes(h, -1, -2), (cfg.hc_mult, 1))


def collapse(cfg: LMConfig, x):
    """[.., n C, T] -> [.., T, C]: the streams summed."""
    streams = x.reshape(x.shape[:-2] + (cfg.hc_mult, cfg.hidden, -1))
    return jnp.swapaxes(jnp.sum(streams, -3), -1, -2)


def _column_sums(cfg: LMConfig, x, dy, v, du):
    """The 2n + n^2 sums over a token's column that the coefficients'
    cotangents are, of products of what one pass reads once (``X``,
    ``dX'``, ``v``, ``du``): ``(d H_pre [n, T], d H_post [n, T], d H_res
    [n, n, T])``."""
    n = cfg.hc_mult
    kernels = _kernels(_tokens(x), cfg.hidden)
    if kernels is not None:
        rows = kernels.sums(_operand(cfg, x), _operand(cfg, dy), v, du)
        return rows[:n], rows[n:2 * n], rows[2 * n:].reshape(n, n, -1)
    xs, dys = _streams(cfg, x), _streams(cfg, dy)
    return (jnp.stack([jnp.sum(du * xj, 0) for xj in xs]),
            jnp.stack([jnp.sum(dyi * v, 0) for dyi in dys]),
            jnp.stack([jnp.stack([jnp.sum(dyi * xj, 0) for xj in xs])
                       for dyi in dys]))


def _pull_coefficients(cfg: LMConfig, hc, mix, kept, d_mix):
    """The coefficients' cotangents ``(d H_pre [n, T], d H_post [n, T], d
    H_res [n, n, T])`` back to ``(d_raw [2n + n^2, T], the mixer's d b
    and d a)``: Sinkhorn's rounds, the clamp, the sigmoids."""
    n = cfg.hc_mult
    pre, post, _ = mix
    _, raw, inside, pull_res = kept
    d_pre, d_post, d_res = d_mix
    d_logits = jnp.concatenate([
        d_pre * pre * (1.0 - pre), d_post * post * (1.0 - 0.5 * post),
        jnp.where(inside, pull_res(d_res)[0].reshape(n * n, -1), 0.0)])
    groups = ((0, n), (n, 2 * n), (2 * n, 2 * n + n * n))
    d_a = jnp.stack([jnp.sum(d_logits[lo:hi] * raw[lo:hi])
                     for lo, hi in groups])
    d_raw = jnp.concatenate([hc["a"][k] * d_logits[lo:hi]
                             for k, (lo, hi) in enumerate(groups)])
    return d_raw, jnp.sum(d_logits, 1), d_a


def _pull_streams(cfg: LMConfig, phi, x, dy, du, mix, d_product, shrink,
                  into=None):
    """The last pass: ``(dX, d phi)``, ``dX_j = sum_i H_res[i, j] dX'_i +
    H_pre[j] du + phi_j^T d - X_j shrink`` with ``d`` the cotangent of
    ``phi X`` and ``shrink`` [1, T] the norm's pull; ``dX`` [n C, T], or
    ``into``'s stack with it as that sequence."""
    n = cfg.hc_mult
    pre, _, res = mix
    kernels = _kernels(_tokens(x), cfg.hidden)
    if kernels is not None:
        dx, d_phi = kernels.dx(
            _operand(cfg, x), _operand(cfg, dy), du, phi, d_product,
            kernels.spread(res.reshape(n * n, -1)), kernels.spread(pre),
            kernels.spread(shrink), into and _operand(cfg, into))
        return _result(dx, into), d_phi
    d_phi = jax.lax.dot_general(d_product, _whole(x),
                                (((1,), (1,)), ((), ())), precision="highest")
    g = jax.lax.dot_general(phi, d_product, (((0,), (0,)), ((), ())),
                            precision="highest")
    dys = _streams(cfg, dy)
    return _placed(jnp.concatenate([
        total([res[i, j][None] * dyi for i, dyi in enumerate(dys)])
        + pre[j:j + 1] * du + gj - xj * shrink
        for j, (xj, gj) in enumerate(zip(_streams(cfg, x),
                                         _streams(cfg, g)))], axis=0),
                   into), d_phi


def sublayer_vjp(cfg: LMConfig, hc, x, f_vjp, into=None, pull_into=None):
    """One sublayer around ``f_vjp(u) -> (v, aux, pull)``, ``pull(dv) ->
    (du, gradients)``: ``(x', aux, pull)`` with ``pull(dx') -> (dx, the
    mixer's gradients, F's gradients)``. ``x`` and ``dx'`` are [n C, T] or
    an ``Of``; ``x'`` is [n C, T], or ``into``'s stack with it as that
    sequence, and ``dx`` likewise by ``pull_into``. The pull is written
    out (the module's docstring has its equations and its passes over
    the streams); nothing here is differentiated but Sinkhorn, by its
    own rule."""
    with jax.named_scope(SCOPE):
        mix, kept = coefficients(cfg, hc, x)
        pre, post, res = mix
        u = _apart(read(cfg, x, pre))
    v, aux, pull_f = f_vjp(u)
    with jax.named_scope(SCOPE):
        v_streams = _apart(v).astype(F32)
        y = write(cfg, x, post, res, v_streams, into)

    def pull(dy):
        with jax.named_scope(SCOPE):
            dv = _apart(_pull_write_to_v(cfg, dy, post).astype(v.dtype))
        du, grads = pull_f(dv)
        with jax.named_scope(SCOPE):
            du = _apart(du).astype(F32)
            d_raw, d_b, d_a = _pull_coefficients(
                cfg, hc, mix, kept, _column_sums(cfg, x, dy, v_streams, du))
            s, raw = kept[:2]
            # the norm's pull wants mean(g r) with g = phi^T d_raw, and
            # g . r = d_raw . raw: a [1, T] row, no pass over the streams
            m = jnp.sum(d_raw * raw, 0, keepdims=True) \
                / (cfg.hc_mult * cfg.hidden)
            dx, d_phi = _pull_streams(cfg, hc["phi"], x, dy, du, mix,
                                      d_raw * s, s * s * m, pull_into)
        return dx, {"phi": d_phi, "b": d_b, "a": d_a}, grads

    return y, aux, pull


# -- the layer ----------------------------------------------------------------------

def _mixer(small, sub):
    return {k: small[f"{sub}_{k}"] for k in MIXER}


def layer_vjp(cfg: LMConfig, sparse: int, mats, small, x, pos=None,
              into=None, pull_into=None):
    """One sequence ``x`` ([n C, T] or an ``Of``) through one layer
    (``sparse``: its feed-forward's kind): ``(y, aux, pull)``, ``aux`` the
    sparse feed-forward's (None in a dense layer), ``pull(dy) -> (dx,
    matrix gradients, small gradients)``; the router's bias gets none.
    ``y`` is [n C, T], or ``into``'s stack with it as that sequence;
    ``dx`` likewise by ``pull_into``."""
    sinks = {name: jnp.zeros(w.shape, F32) for name, w in mats.items()}

    def attention(u):
        v, pull = latent.attention_vjp(cfg, mats, sinks, small, u, pos)

        def pull_both(dv):
            du, d_mats, d_small = pull(dv)
            return du, (d_mats, d_small)

        return v, None, pull_both

    a, _, pull_attention = sublayer_vjp(cfg, _mixer(small, "hc_attn"), x,
                                        attention, pull_into=pull_into)
    y, aux, pull_ffn = sublayer_vjp(
        cfg, _mixer(small, "hc_ffn"), a,
        lambda u: lm.feed_forward_vjp(cfg, sparse, mats, sinks, small, u),
        into=into)

    def pull(dy):
        da, d_hc_ffn, (d_mats_ffn, d_small_ffn) = pull_ffn(dy)
        dx, d_hc_attn, (d_mats_attn, d_small_attn) = pull_attention(da)
        d_small = {**d_small_attn, **d_small_ffn}
        for sub, d_hc in (("hc_attn", d_hc_attn), ("hc_ffn", d_hc_ffn)):
            d_small.update({f"{sub}_{k}": g for k, g in d_hc.items()})
        return dx, {**d_mats_attn, **d_mats_ffn}, d_small

    return y, aux, pull


def layer_forward(cfg: LMConfig, sparse: int, mats, small, x, pos=None,
                  into=None):
    """``model.layer_forward``'s results for this family's layer."""
    y, aux, _ = layer_vjp(cfg, sparse, mats, small, x, pos, into)
    return (y,) + lm.layer_stats(cfg, sparse, aux)


def layer_grads(cfg: LMConfig, sparse: int, mats, small, x, dy, pos=None,
                into=None):
    """``model.layer_grads``'s results for this family's layer."""
    return layer_vjp(cfg, sparse, mats, small, x, pos, pull_into=into)[2](dy)

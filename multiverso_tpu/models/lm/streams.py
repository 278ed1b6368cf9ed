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

The feed-forward ``F`` is a dense MLP (``mv.lm.dense_mlp``) or, in a
sparse layer, the router (sigmoid scores, chosen through the bias:
``model.route``), the held routed experts (``model.routed_experts``) and
the shared expert (``mv.lm.shared_expert``), all on one normed ``h``.

``layer_vjp`` is the layer and what pulls a cotangent back through it;
the forward programs call it and drop the pull. Scope ``mv.lm.hc``: the
coefficients, Sinkhorn and the two mixes, forward and backward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import latent
from . import model as lm
from .model import F32, LMConfig

SCOPE = "mv.lm.hc"
SUBLAYERS = ("hc_attn", "hc_ffn")
MIXER = ("phi", "b", "a")
DENSE = ("w_gate", "w_up", "w_down")
SHARED = ("ws_gate", "ws_up", "ws_down")


def sinkhorn(logits, iters: int, eps: float):
    """[n, n, T] (row, column, token) -> the same made (nearly) doubly
    stochastic a token: ``exp``, then ``iters`` rounds of row, then
    column normalisation."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, 1, keepdims=True) + eps)
        m = m / (jnp.sum(m, 0, keepdims=True) + eps)
    return m


def _streams(cfg: LMConfig, x):
    c = cfg.hidden
    return [x[j * c:(j + 1) * c] for j in range(cfg.hc_mult)]


def coefficients(cfg: LMConfig, hc, x):
    """``(H_pre [n, T], H_post [n, T], H_res [n, n, T])`` of a sublayer
    whose mixer ``hc`` is ``{"phi", "b", "a"}`` for ``x`` [n C, T]."""
    n = cfg.hc_mult
    x = x.astype(F32)
    r = x * jax.lax.rsqrt(jnp.mean(x * x, 0, keepdims=True) + cfg.eps)
    raw = jnp.dot(hc["phi"], r, precision="highest")
    b, a = hc["b"][:, None], hc["a"]
    pre = jax.nn.sigmoid(a[0] * raw[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * raw[n:2 * n] + b[n:2 * n])
    res = jnp.clip(a[2] * raw[2 * n:] + b[2 * n:], *cfg.hc_clamp)
    return pre, post, sinkhorn(res.reshape(n, n, -1), cfg.hc_iters,
                               cfg.hc_eps)


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


@jax.custom_vjp
def _turned(v):
    """[T, C] <-> [C, T] between a sublayer ``F`` and the streams."""
    return _apart(v)


_turned.defvjp(lambda v: (_apart(v), None), lambda _, g: (_apart(g),))


def read(cfg: LMConfig, hc, x):
    """A sublayer's input and what ``write`` mixes by: ``(u [T, C],
    (H_post, H_res))``."""
    pre, post, res = coefficients(cfg, hc, x)
    u = sum(pre[j:j + 1] * xj for j, xj in enumerate(_streams(cfg, x)))
    return _turned(u), (post, res)


def write(cfg: LMConfig, x, mix, v):
    """The streams after a sublayer whose ``F`` gave ``v`` [T, C]."""
    post, res = mix
    xs, v = _streams(cfg, x), _turned(v)
    return jnp.concatenate([
        sum(res[i, j][None] * xj for j, xj in enumerate(xs))
        + post[i:i + 1] * v for i in range(cfg.hc_mult)], axis=0)


def expand(cfg: LMConfig, h):
    """[.., T, C] -> [.., n C, T]: every stream starts as ``h``."""
    return jnp.tile(jnp.swapaxes(h, -1, -2), (cfg.hc_mult, 1))


def collapse(cfg: LMConfig, x):
    """[.., n C, T] -> [.., T, C]: the streams summed."""
    streams = x.reshape(x.shape[:-2] + (cfg.hc_mult, cfg.hidden, -1))
    return jnp.swapaxes(jnp.sum(streams, -3), -1, -2)


def sublayer_vjp(cfg: LMConfig, hc, x, f_vjp):
    """One sublayer around ``f_vjp(u) -> (v, aux, pull)``, ``pull(dv) ->
    (du, gradients)``: ``(x', aux, pull)`` with ``pull(dx') -> (dx, the
    mixer's gradients, F's gradients)``."""
    with jax.named_scope(SCOPE):
        (u, mix), pull_read = jax.vjp(lambda hc, x: read(cfg, hc, x), hc, x)
    v, aux, pull_f = f_vjp(u)
    with jax.named_scope(SCOPE):
        y, pull_write = jax.vjp(lambda x, mix, v: write(cfg, x, mix, v),
                                x, mix, v)

    def pull(dy):
        with jax.named_scope(SCOPE):
            dx, d_mix, dv = pull_write(dy)
        du, grads = pull_f(dv)
        with jax.named_scope(SCOPE):
            d_hc, dx_read = pull_read((du, d_mix))
            dx = dx + dx_read
        return dx, d_hc, grads

    return y, aux, pull


# -- the two feed-forwards -------------------------------------------------------

def dense_vjp(cfg: LMConfig, mats, sinks, small, u):
    with jax.named_scope("mv.lm.dense_mlp"):
        v, pull_mlp = jax.vjp(
            lambda s, g, u: lm.gated_mlp(cfg, mats, s, DENSE,
                                         lm.rmsnorm(u, g, cfg.eps)),
            {n: sinks[n] for n in DENSE}, small["norm_ffn"], u)

    def pull(dv):
        with jax.named_scope("mv.lm.dense_mlp"):
            d_mats, d_norm, du = pull_mlp(dv)
        return du, (d_mats, {"norm_ffn": d_norm})

    return v, None, pull


def sparse_vjp(cfg: LMConfig, mats, sinks, small, u):
    """Router, held routed experts and shared expert on one normed ``h``:
    ``aux`` is ``(ids [T, k], held experts' assignments [held], every
    router output's assignments [n_experts])``."""
    routed = {n: sinks[n] for n in DENSE}
    with jax.named_scope("mv.lm.experts"):
        h, pull_norm = jax.vjp(lambda g, u: lm.rmsnorm(u, g, cfg.eps),
                               small["norm_ffn"], u)
    with jax.named_scope("mv.lm.router"):
        weights, pull_router, ids = jax.vjp(
            lambda r, h: lm.route(cfg, r, h, small["router_bias"])[::-1],
            small["router"], h, has_aux=True)
        load = lm.router_load(cfg, ids)
    with jax.named_scope("mv.lm.experts"):
        y, pull_experts, sizes = jax.vjp(
            lambda s, h, w: lm.routed_experts(cfg, mats, s, h.astype(lm.BF16),
                                              ids, w),
            routed, h, weights, has_aux=True)
    pull_shared = None
    if cfg.shared_width:
        with jax.named_scope("mv.lm.shared_expert"):
            shared, pull_shared = jax.vjp(
                lambda s, h: lm.gated_mlp(cfg, mats, s, SHARED, h),
                {n: sinks[n] for n in SHARED}, h)
        y = y + shared

    def pull(dy):
        with jax.named_scope("mv.lm.experts"):
            d_mats, dh, dw = pull_experts(dy)
        with jax.named_scope("mv.lm.router"):
            d_router, dh_router = pull_router(dw)
        dh = dh + dh_router
        if pull_shared is not None:
            with jax.named_scope("mv.lm.shared_expert"):
                d_shared, dh_shared = pull_shared(dy)
            d_mats, dh = {**d_mats, **d_shared}, dh + dh_shared
        with jax.named_scope("mv.lm.experts"):
            d_norm, du = pull_norm(dh)
        return du, (d_mats, {"norm_ffn": d_norm, "router": d_router})

    return y, (ids, sizes, load), pull


# -- the layer ----------------------------------------------------------------------

def _mixer(small, sub):
    return {k: small[f"{sub}_{k}"] for k in MIXER}


def layer_vjp(cfg: LMConfig, sparse: int, mats, small, x, pos=None):
    """One sequence ``x`` [n C, T] through one layer (``sparse``: its
    feed-forward's kind): ``(y, aux, pull)``, ``aux`` the sparse
    feed-forward's (None in a dense layer), ``pull(dy) -> (dx, matrix
    gradients, small gradients)``; the router's bias gets none."""
    sinks = {name: jnp.zeros(w.shape, F32) for name, w in mats.items()}
    ffn = sparse_vjp if sparse else dense_vjp

    def attention(u):
        v, pull = latent.attention_vjp(cfg, mats, sinks, small, u, pos)

        def pull_both(dv):
            du, d_mats, d_small = pull(dv)
            return du, (d_mats, d_small)

        return v, None, pull_both

    a, _, pull_attention = sublayer_vjp(cfg, _mixer(small, "hc_attn"), x,
                                        attention)
    y, aux, pull_ffn = sublayer_vjp(
        cfg, _mixer(small, "hc_ffn"), a,
        lambda u: ffn(cfg, mats, sinks, small, u))

    def pull(dy):
        da, d_hc_ffn, (d_mats_ffn, d_small_ffn) = pull_ffn(dy)
        dx, d_hc_attn, (d_mats_attn, d_small_attn) = pull_attention(da)
        d_small = {**d_small_attn, **d_small_ffn}
        for sub, d_hc in (("hc_attn", d_hc_attn), ("hc_ffn", d_hc_ffn)):
            d_small.update({f"{sub}_{k}": g for k, g in d_hc.items()})
        return dx, {**d_mats_attn, **d_mats_ffn}, d_small

    return y, aux, pull


def layer_stats(cfg: LMConfig, sparse: int, aux):
    """What a forward program reports of one sequence: ``(stats, ids)``.
    A sparse layer's ``stats`` int32 [2 + n_experts]: assignments on held
    experts, the fullest held expert's, then every router output's; a
    dense layer's two zeros and no ids."""
    if not sparse:
        return jnp.zeros((2,), jnp.int32), jnp.zeros((0, cfg.top_k),
                                                     jnp.int32)
    ids, sizes, load = aux
    return jnp.concatenate(
        [jnp.stack([jnp.sum(sizes), jnp.max(sizes)]), load]), ids


def layer_forward(cfg: LMConfig, sparse: int, mats, small, x, pos=None):
    """``model.layer_forward``'s results for this family's layer."""
    y, aux, _ = layer_vjp(cfg, sparse, mats, small, x, pos)
    return (y,) + layer_stats(cfg, sparse, aux)


def layer_grads(cfg: LMConfig, sparse: int, mats, small, x, dy, pos=None):
    """``model.layer_grads``'s results for this family's layer."""
    return layer_vjp(cfg, sparse, mats, small, x, pos)[2](dy)

"""The gated short convolution (the mixer of ``model_type: lfm2_moe``'s
``conv`` layers, LiquidAI's LFM2-8B-A1B: ``Lfm2MoeShortConv``), as the
attention of a layer on the plain residual: the first mixer in
``models/lm/`` that is not attention at all. For a sublayer's input ``x``
[T, hidden], ``h = RMSNorm(x)``:

    (B, C, X) = split_3(h W_in)             W_in [hidden, 3 hidden]
    z = B * X
    c[t] = sum_j w[:, j] z[t - (n - 1) + j] j = 0 .. n - 1, ``n =
                                            cfg.conv_taps`` (3); ``z`` zero
                                            before the sequence's first
                                            position; a channel reads no other
                                            channel; no bias, NO activation
    F(x) = (C * c) W_out                    W_out [hidden, hidden]

A position reads itself and the ``n - 1`` before it and nothing else: the
layer's reach is exactly ``n``, whatever the sequence's length, and nothing
of it is quadratic. It reads no position's number either (``rope_layout`` 0).

**Precision.** The two products take bfloat16 inputs and accumulate in
float32 (``model.mm``); the gates and the taps between them (``chain``) are
float32, elementwise: three reads of a [T, hidden] array and one write
forward, the like backward. On a TPU the compiler fuses most of it into the
two products' own fusions (PERF.md section 5: a tenth of the mixer's time is
under the chain's scope alone), so no kernel is written for it.

Scopes: ``mv.lm.attn.shortconv`` (norm, ``W_in``, ``W_out``),
``mv.lm.attn.shortconv.taps`` (``chain``: both gates and the taps), the
backward pass under the same names (``attention_vjp`` differentiates the
parts one by one, each entered outside the differentiated function).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import model as lm
from .model import LMConfig

SCOPE = "mv.lm.attn.shortconv"
MATRICES = ("w_in", "w_out")
TAPS = "conv_w"     # [hidden, taps] float32, a channel a row: a small tensor


def shapes(cfg: LMConfig) -> dict:
    """A convolution layer's mixer as the server stores it."""
    h = cfg.hidden
    return {"w_in": (h, 3 * h), "w_out": (h, h), TAPS: (h, cfg.conv_taps)}


def taps(z, w):
    """The causal depthwise convolution over positions: z [T, channels], w
    [channels, n]; position ``t`` reads ``t - n + 1 .. t``, zeros before
    the sequence. No activation follows."""
    t, n = z.shape[0], w.shape[1]
    padded = jnp.pad(z, ((n - 1, 0), (0, 0)))
    return sum(padded[j:j + t] * w[:, j] for j in range(n))


def chain(w, bcx):
    """Between the two products: ``bcx`` [T, 3 hidden] float32 as ``W_in``
    leaves it -> ``C * taps(B * X)`` [T, hidden]."""
    b, c, x = jnp.split(bcx, 3, axis=-1)
    return c * taps(b * x, w)


def attention_vjp(cfg: LMConfig, mats, sinks, small, x):
    """``F(x)`` for one sequence and what pulls a cotangent back through
    it: ``(F(x), counts, pull)``, ``pull(d) -> (dx, matrix gradients, small
    gradients)``, as ``delta.attention_vjp`` gives them (no counts here).
    What lives from the forward pass to the pull is ``h W_in`` and what
    the chain's transpose keeps of it."""
    with jax.named_scope(SCOPE):
        bcx, pull_in = jax.vjp(
            lambda s, norm, x: lm.mm(lm.rmsnorm(x, norm, cfg.eps),
                                     mats["w_in"], s),
            sinks["w_in"], small["norm_attn"], x)
    with jax.named_scope(SCOPE + ".taps"):
        mixed, pull_chain = jax.vjp(chain, small[TAPS], bcx)
    with jax.named_scope(SCOPE):
        out, pull_out = jax.vjp(
            lambda s, mixed: lm.mm(mixed, mats["w_out"], s),
            sinks["w_out"], mixed)

    def pull(d_out):
        with jax.named_scope(SCOPE):
            d_w_out, d_mixed = pull_out(d_out)
        with jax.named_scope(SCOPE + ".taps"):
            d_taps, d_bcx = pull_chain(d_mixed)
        with jax.named_scope(SCOPE):
            d_w_in, d_norm, dx = pull_in(d_bcx)
        return (dx, {"w_in": d_w_in, "w_out": d_w_out},
                {"norm_attn": d_norm, TAPS: d_taps})

    return out, {}, pull


def mix(cfg: LMConfig, mats, sinks, small, x):
    """``F(x)`` for one sequence."""
    return attention_vjp(cfg, mats, sinks, small, x)[0]

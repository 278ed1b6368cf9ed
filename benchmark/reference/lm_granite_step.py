"""Plain reference for one training step of one chip's share of
granite-4.0-h-micro (ibm-granite 2025,
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json,
``model_type: granitemoehybrid``): the forward pass, the loss, its gradients
by ``jax.grad``/``jax.vjp`` and Adam, in float32 ``jax.numpy`` at
``default_matmul_precision("highest")`` (callers set it: ``with
PRECISION:``), with no kernel, no chunked scan, no bfloat16 and nothing
imported from the program (``multiverso_tpu/models/lm``). Adam, the norm,
the blocks of queries under a causal mask and the gated MLP are
lm_mla_step.py's, which the references share.

With ``h`` a layer's input [T, 2048], r = ``residual_multiplier`` 0.22, ``x =
RMSNorm(h; g_in)``, ``a = h + r Mix(x)``, ``u = RMSNorm(a; g_post)``, ``y = a
+ r MLP(u)``, eps 1e-5; the first layer's input ``12 E[ids]``
(``embedding_multiplier``):

**A state-space layer's mixer** (``layer_types`` ``mamba``; ``mamba_f``; the
released ``GraniteMoeHybridMambaLayer``: ``in_proj``, ``conv1d`` with ``groups
= conv_dim`` and left padding, ``act``, the SSD recurrence,
``MambaRMSNormGated``, ``out_proj``):

    (z, xBC, dt) = split(x W_in)        W_in [2048, 4096 + 4352 + 64]
    xBC = silu(w[:, 0] xBC[t-3] + w[:, 1] xBC[t-2] + w[:, 2] xBC[t-1]
               + w[:, 3] xBC[t] + b_c)  FOUR SHIFTED SUMS written out
                                        (``taps``), zeros before the sequence
    (X, B, C) = split(xBC)              X [T, 64, 64]; B, C [T, 128]: ONE
                                        group, every head reads the same two
    dt = softplus(dt + dt_bias) [T, 64];  A = -exp(A_log) [64]
    S_i[t] = exp(dt_t A_i) S_i[t-1] + dt_t X_t (x) B_t     a head [64 x 128],
    Y_t = S_i[t] C_t + D_i X_t          S = 0 before the sequence: THE
                                        RECURRENCE, position by position
                                        (``recurrence``: ``lax.scan`` over t,
                                        blocks of positions under
                                        ``jax.checkpoint``; NOT the chunked
                                        form, so the program and this file
                                        cannot share a mistake)
    G = Y * silu(z);  N = G rsqrt(mean_4096(G^2) + eps) g_n   the gate
                                        INSIDE the norm, the mean over ALL
                                        4096 lanes (``mamba_n_groups`` 1)
    Mix(x) = N W_out                    W_out [4096, 2048]

**An attention layer's** (``attention``; ``attention_f``): ``q = x W_q`` [T,
32, 64], ``k = x W_k``, ``v = x W_v`` [T, 8, 64]; no head norm, NO rotary
turn (``position_embedding_type: "nope"``), no bias; query head ``i`` reads
key-value head ``i // 4``; causal softmax of ``q . k *
attention_multiplier`` (0.015625 = 1/64, not 64^-1/2) as a masked matrix a
block of queries; ``Mix(x) = o W_o``.

**Feed-forward, every layer**: ``MLP(u) = W_d (silu(u W_g) * (u W_u))``,
width ``shared_intermediate_size`` 8192. No router, no experts.

**One table** (``tie_word_embeddings``): ``step_loss`` takes ``params``
WITHOUT a ``head``; the table is used twice, for the rows (times 12) and as
``E^T / logits_scaling`` after the final norm, so ``jax.grad`` gives it the
sum of both uses' gradients by construction.

Departures from the published model, each the configuration's
(benchmark/configs/granite-4.0-h-micro-l10.json) and the program's alike:
the first ten layers of forty and a slice of the vocabulary's rows; every
layer is whole.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.lm_mla_step import (  # noqa: F401 - callers use them
    PRECISION, adam, adam_rows, attention, gated, rmsnorm)

KINDS = {"mamba": "ssd", "attention": "gqa"}


def taps(x, w, bias):
    """x [T, channels], w [channels, 4], bias [channels]: position ``t``
    reads ``t - 3 .. t``, zeros before the sequence; written out."""
    nothing = jnp.zeros_like(x[:1])
    assert w.shape[1] == 4, w.shape
    back = [x] + [jnp.concatenate([nothing] * j + [x[:-j]])
                  for j in (1, 2, 3)]
    return (w[:, 0] * back[3] + w[:, 1] * back[2] + w[:, 2] * back[1]
            + w[:, 3] * back[0] + bias)


def recurrence(x, dt, a, b, c, block=256):
    """``Y_t = S_t C_t`` (no skip) for x [T, H, P], dt [T, H] (> 0), a [H]
    (< 0), b, c [T, N]: the state a head [P x N] position by position."""
    t, heads, lanes = x.shape
    block = min(block, t)
    assert t % block == 0, (t, block)

    def position(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.sum(state * c_t[None, None, :], axis=-1)

    @jax.checkpoint
    def positions(state, run):
        return jax.lax.scan(position, state, run)

    runs = tuple(v.reshape(t // block, block, *v.shape[1:])
                 for v in (x, dt, b, c))
    _, y = jax.lax.scan(positions,
                        jnp.zeros((heads, lanes, b.shape[-1]), x.dtype), runs)
    return y.reshape(t, heads, lanes)


def mamba_f(c, p, h):
    t, heads, lanes, n = h.shape[0], c["ssd_heads"], c["ssd_head_dim"], \
        c["ssd_state"]
    inner = heads * lanes
    x = rmsnorm(h, p["norm_attn"], c["eps"])
    z, xbc, dt = jnp.split(x @ p["w_in"], [inner, 2 * inner + 2 * n], axis=-1)
    xbc = jax.nn.silu(taps(xbc, p["conv_w"], p["conv_b"]))
    into, b, cc = jnp.split(xbc, [inner, inner + n], axis=-1)
    into = into.reshape(t, heads, lanes)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = recurrence(into, dt, -jnp.exp(p["a_log"]), b, cc) \
        + p["d"][:, None] * into
    g = y.reshape(t, inner) * jax.nn.silu(z)
    normed = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                               + c["eps"]) * p["norm_g"]
    return normed @ p["w_out"]


def attention_f(c, p, h):
    t, heads, kv, d = h.shape[0], c["heads"], c["kv_heads"], c["head_dim"]
    x = rmsnorm(h, p["norm_attn"], c["eps"])
    q = (x @ p["wq"]).reshape(t, heads, d)
    k = (x @ p["wk"]).reshape(t, kv, d)
    v = (x @ p["wv"]).reshape(t, kv, d)
    # query head i reads key-value head i // (heads / kv)
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    o = attention(q, k, v, c["attn_scale"], block=min(1024, t))
    return o.reshape(t, -1) @ p["wo"]


def layer(c, kind, p, x):
    """One sequence ``x`` [T, hidden] through one layer of ``kind`` (``ssd``
    | ``gqa``: the CONFIGURATION's ``layer_types`` say, ``kinds``) whose
    tensors ``p`` are named and shaped as the server's tables."""
    r = c["residual_scale"]
    a = x + r * (mamba_f if kind == "ssd" else attention_f)(c, p, x)
    u = rmsnorm(a, p["norm_ffn"], c["eps"])
    return a + r * gated(u, p["w_gate"], p["w_up"], p["w_down"])


def head_loss(c, table, norm, x, targets, total):
    """Sum of the cross entropy of ``targets`` over ``x`` [N, hidden], over
    ``total`` (the mean's denominator): the logits are ``RMSNorm(x) E^T /
    logits_scaling``."""
    logits = rmsnorm(x, norm, c["eps"]) @ table.T / c["logits_scale"]
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked) / total


def embed(c, table, ids):
    return c["embed_scale"] * table[ids]


def step_loss(c, params, tokens):
    """The whole step's loss for ``tokens`` [B, T+1]: for ``jax.grad`` at
    small sizes. ``params`` is ``{"embedding", "layers": [..],
    "final_norm"}``: the ONE table is the head too."""
    ids, targets = tokens[:, :-1], tokens[:, 1:]
    x = embed(c, params["embedding"], ids)
    for kind, p in zip(c["kinds"], params["layers"]):
        x = jax.vmap(lambda seq, p=p, k=kind: layer(c, k, p, seq))(x)
    return head_loss(c, params["embedding"], params["final_norm"],
                     x.reshape(-1, x.shape[-1]), targets.reshape(-1),
                     targets.size)


def tied_gradient(c, d_head, ids, d_input):
    """The one table's gradient from its two uses' (what ``jax.grad`` of
    ``step_loss`` gives whole): the head's [vocab, hidden] and the first
    layer's input's a position, which reaches a row times
    ``embedding_multiplier``."""
    return d_head.at[ids.reshape(-1)].add(
        c["embed_scale"] * d_input.reshape(-1, d_input.shape[-1]))


def kinds(config: dict):
    """Each held layer's kind of mixer, from ``layer_types`` as published:
    ``"ssd"`` | ``"gqa"``."""
    n = int(config["num_hidden_layers"])
    return [KINDS[t] for t in config["layer_types"][:n]]


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's keys (the
    published ``config.json``'s)."""
    hidden, heads = int(config["hidden_size"]), int(
        config["num_attention_heads"])
    return {
        "hidden": hidden, "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or hidden // heads),
        "kinds": kinds(config),
        "ssd_heads": int(config["mamba_n_heads"]),
        "ssd_head_dim": int(config["mamba_d_head"]),
        "ssd_state": int(config["mamba_d_state"]),
        "layers": int(config["num_hidden_layers"]),
        "attn_scale": float(config["attention_multiplier"]),
        "residual_scale": float(config["residual_multiplier"]),
        "embed_scale": float(config["embedding_multiplier"]),
        "logits_scale": float(config["logits_scaling"]),
        "eps": float(config["rms_norm_eps"])}

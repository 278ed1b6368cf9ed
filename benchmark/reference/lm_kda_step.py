"""Plain reference for one training step of one chip's share of
Kimi-Linear-48B-A3B (Moonshot 2025,
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json,
``model_type: kimi_linear``; the Kimi Linear report, arXiv 2510.26692): the
forward pass, the loss, its gradients by ``jax.grad``/``jax.vjp``, Adam and
the router bias's step, in float32 ``jax.numpy`` at
``default_matmul_precision("highest")`` (callers set it: ``with
PRECISION:``), with no kernel, NO CHUNKS, no sorting of tokens by expert, no
bfloat16 and nothing imported from the program
(``multiverso_tpu/models/lm``). Adam and the norm are lm_step.py's, the
latent attention's blocks of queries, the router through its bias, the
experts and the head lm_mla_step.py's, which the references share.

**A delta layer's attention** (``linear_attn_config.kda_layers``, counted
from 1; ``delta_f``), ``h = RMSNorm(x)``, heads ``i`` of ``num_heads`` 32,
K = V = ``head_dim`` 128, ``conv`` the causal depthwise convolution over
positions with ``short_conv_kernel_size`` 4 weights a channel and no bias
(``y[t] = sum_j w[c, j] x[t - 3 + j]``, zero before the sequence; four
shifted sums, ``conv``):

    q~, k~, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))
    q_i = q~_i / |q~_i|_2 * 128^-1/2,   k_i = k~_i / |k~_i|_2
    g    = -exp(A_log_i) softplus((h W_fa) W_fb + dt_bias)   a CHANNEL
    beta = sigmoid(h W_b)                                    a head
    S_i[t] = (I - beta k k^T) Diag(exp g) S_i[t-1] + beta k v^T,  S_i[-1] = 0
    o_i[t] = S_i[t]^T q_i[t]
    F = concat_i(RMSNorm(o_i; g_o) * sigmoid(((h W_ga) W_gb)_i)) W_o

The recurrence is computed POSITION BY POSITION (``recurrence``:
``jax.lax.scan`` over T with the state [heads, 128, 128] as its carry), and
its gradients come from differentiating that scan: nothing of the
program's chunked algebra is here. For memory the scan is cut into
segments under ``jax.checkpoint`` (the backward pass keeps the state at
each segment's start and recomputes the positions inside one).

**A latent layer's attention** (``full_attn_layers``; ``latent_f``): ``q =
h W_q`` [32 x (128 + 64)] (``q_lora_rank`` null: no latent, no norm),
``[c_kv | k_r] = h W_kva`` [512 | 64], ``[k_n | v] = RMSNorm(c_kv) W_kvb``
[128 | 128] a head, NO rotary turn (``mla_use_nope``; ``k_r`` one for all
heads), score ``(q_n . k_n + q_r . k_r) 192^-1/2``, causal.

**Feed-forward** (lm_mla_step.py ``feed_forward``). Layers ``<
first_k_dense_replace``: ``W_d (silu(h W_g) * (h W_u))``, width 9216. The
others: ``s = sigmoid(h W_r)`` [256]; ``S`` = the 8 largest of ``s + bias``
(one group); ``w_e = routed_scaling_factor s_e / sum_S s``; ``y = sum_{e in
S, e held} w_e E_e(h) + E_shared(h)``. The bias gets no gradient: after a
step ``bias_e += gamma sign(mean(load) - load_e)``, gamma ASSUMED 0.001.

Departures from the published model, each the configuration's
(benchmark/configs/kimi-linear-48b-a3b-l5.json) and the program's alike:
- **the share**: experts ``first .. first + held - 1`` of the 256 (``w_e``
  over all eight), a slice of the vocabulary's rows; what the absent
  experts would add is left out; attention of both kinds, convolutions,
  router, shared expert, dense MLP and norms are whole;
- the eight may be GIVEN (``chosen``), as in lm_step.py;
- every held expert is computed over every token and weighted by ``w_e``
  or by 0.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.lm_mla_step import (  # noqa: F401 - callers use them
    PRECISION, adam, adam_rows, attention, bias_step, feed_forward,
    head_loss, load_of, rmsnorm, routing)

CONVS = ("conv_q", "conv_k", "conv_v")


def conv(x, w):
    """x [T, channels], w [channels, n]: position ``t`` reads ``t - n + 1
    .. t``, zero before the sequence."""
    t, n = x.shape[0], w.shape[1]
    y = jnp.zeros_like(x)
    for j in range(n):
        back = n - 1 - j    # weight j reads ``back`` positions back
        y = y + w[:, j] * jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[:t - back]])
    return y


def recurrence(q, k, v, g, beta, segment=64):
    """The delta rule position by position: q, k, g [T, heads, K], v [T,
    heads, V], beta [T, heads] -> o [T, heads, V]."""
    t, heads, lanes = q.shape
    segment = segment if t % segment == 0 else t

    def position(state, at):
        q, k, v, g, beta = at
        state = jnp.exp(g)[..., None] * state
        write = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k))
        state = state + k[..., None] * write[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q)

    @jax.checkpoint
    def positions(state, part):
        return jax.lax.scan(position, state, part)

    _, o = jax.lax.scan(
        positions, jnp.zeros((heads, lanes, v.shape[-1]), q.dtype),
        tuple(a.reshape(t // segment, segment, *a.shape[1:])
              for a in (q, k, v, g, beta)))
    return o.reshape(t, heads, v.shape[-1])


def delta_inputs(c, p, h):
    """``(q, k, v, g [T, heads, K], beta [T, heads])`` of the normed input."""
    t, heads, d = h.shape[0], c["kda_heads"], c["kda_dim"]
    q, k, v = (jax.nn.silu(conv(h @ p[w], p[cw])).reshape(t, heads, d)
               for w, cw in zip(("wq", "wk", "wv"), CONVS))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(p["a_log"])[None, :, None] * jax.nn.softplus(
        (h @ p["w_fa"]) @ p["w_fb"] + p["dt_bias"]).reshape(t, heads, d)
    return q, k, v, g, jax.nn.sigmoid(h @ p["w_beta"])


def delta_f(c, p, u):
    t = u.shape[0]
    h = rmsnorm(u, p["norm_attn"], c["eps"])
    o = recurrence(*delta_inputs(c, p, h))
    gate = jax.nn.sigmoid((h @ p["w_ga"]) @ p["w_gb"])
    return (rmsnorm(o, p["norm_o"], c["eps"]).reshape(t, -1) * gate) @ p["wo"]


def latent_f(c, p, u):
    t, heads = u.shape[0], c["heads"]
    nope, rope, latent = c["nope_dim"], c["rope_dim"], c["kv_rank"]
    h = rmsnorm(u, p["norm_attn"], c["eps"])
    q = (h @ p["wq"]).reshape(t, heads, nope + rope)
    kv_a = h @ p["wkv_a"]
    kv = (rmsnorm(kv_a[:, :latent], p["norm_kv_a"], c["eps"])
          @ p["wkv_b"]).reshape(t, heads, nope + c["v_dim"])
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(kv_a[:, None, latent:], (t, heads, rope))], -1)
    o = attention(q, k, kv[..., nope:], (nope + rope) ** -0.5)
    return o.reshape(t, -1) @ p["wo"]


def layer(c, p, x, chosen=None, own=False):
    """One sequence ``x`` [T, hidden] through one layer whose tensors ``p``
    are named and shaped as the server's tables: a layer with an ``a_log``
    is a delta layer, one without a latent one; one with a ``router``
    sparse, one without dense. With ``own`` also the experts this file
    would choose itself ([T, k]; None in a dense layer), whatever
    ``chosen`` says."""
    a = x + (delta_f if "a_log" in p else latent_f)(c, p, x)
    y = a + feed_forward(c, p, a, chosen)
    if not own:
        return y
    ids = None
    if "router" in p:
        ids = routing(c, p["router"], p["router_bias"],
                      rmsnorm(a, p["norm_ffn"], c["eps"]))[0]
    return y, ids


def step_loss(c, params, tokens, chosen=None):
    """The whole step's loss for ``tokens`` [B, T+1]: for ``jax.grad`` at
    small sizes. ``params`` is ``{"embedding", "layers": [..],
    "final_norm", "head"}``; ``chosen`` per layer [B, T, k] or None."""
    ids, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embedding"][ids]
    for i, p in enumerate(params["layers"]):
        given = None if chosen is None else chosen[i]
        if given is None:
            x = jax.vmap(lambda seq, p=p: layer(c, p, seq))(x)
        else:
            x = jax.vmap(lambda seq, ids, p=p: layer(c, p, seq, ids))(x, given)
    return head_loss(c, params["head"], params["final_norm"],
                     x.reshape(-1, x.shape[-1]), targets.reshape(-1),
                     targets.size)


def kinds(config: dict):
    """Each held layer's kind of attention: ``"kda"`` | ``"mla"``."""
    delta = set(config["linear_attn_config"]["kda_layers"])
    return ["kda" if i in delta else "mla"
            for i in range(1, int(config["num_hidden_layers"]) + 1)]


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's keys (the
    published ``config.json``'s)."""
    linear = config["linear_attn_config"]
    return {
        "hidden": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope_dim": int(config["qk_nope_head_dim"]),
        "rope_dim": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "kda_heads": int(linear["num_heads"]),
        "kda_dim": int(linear["head_dim"]),
        "kda_conv": int(linear["short_conv_kernel_size"]),
        "outputs": int(config["router_outputs"]),
        "top_k": int(config["num_experts_per_token"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "held": int(config["num_experts"]),
        "first_held": int(config.get("first_expert_held", 0)),
        "routed_scale": float(config["routed_scaling_factor"]),
        "bias_rate": float(config["router_bias_rate"]),
        "layers": int(config["num_hidden_layers"]),
        "dense_layers": int(config["first_k_dense_replace"]),
        "eps": float(config["rms_norm_eps"])}

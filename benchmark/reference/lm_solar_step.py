"""Plain reference for one training step of one chip's share of
Solar-Open2-250B (upstage 2026,
https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json,
``model_type: solar_open2``): the forward pass, the loss, its gradients by
``jax.grad``/``jax.vjp``, Adam and the router bias's step, in float32
``jax.numpy`` at ``default_matmul_precision("highest")`` (callers set it:
``with PRECISION:``), with no kernel, NO CHUNKS and no solve, no sorting of
tokens by expert, no bfloat16 and nothing imported from the program
(``multiverso_tpu/models/lm``). Adam and the norm are lm_step.py's, the
blocks of queries under a causal mask, the router through its bias, the
experts and the head lm_mla_step.py's, the convolution and the recurrence
position by position lm_kda_step.py's, which the references share.

With ``x = RMSNorm(h; g_attn)`` for a layer's input ``h`` [T, 4096]:

**A softmax layer's attention** (``gqa_layers``, counted from 0, every
fourth; ``softmax_f``): ``q = x W_q`` [T, 64, 128], ``k = x W_k``, ``v = x
W_v`` [T, 8, 128]; NO turn by position (``use_rope`` false), no q or k norm;
query head ``i`` reads key-value head ``i // 8``; causal softmax of ``q . k
128^-1/2``; ``o <- o * sigmoid(x W_g)``, ``W_g`` [4096, 64 x 128] a gate a
LANE (``use_gqa_gate``; a ``W_g`` [4096, 64] is the other reading, a gate a
head); ``o W_o``.

**A delta layer's attention** (the other three of four; ``delta_f``), heads
``i`` of 64, K = V = 128, ``conv`` the causal depthwise convolution over
positions with 4 weights a channel and no bias:

    q~, k~, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))
    q_i = q~_i / |q~_i|_2 * 128^-1/2,   k_i = k~_i / |k~_i|_2
    g    = -exp(A_log_i) softplus((x W_fa) W_fb + dt_bias)   a CHANNEL
    beta = 2 sigmoid(x W_b)        a head, in (0, 2): ``kda_allow_neg_eigval``
    S_i[t] = (I - beta k k^T) Diag(exp g) S_i[t-1] + beta k v^T,  S_i[-1] = 0
    o_i[t] = S_i[t]^T q_i[t]
    F = concat_i(RMSNorm(o_i; g_o) * sigmoid(((x W_ga) W_gb)_i)) W_o

POSITION BY POSITION (``recurrence``: ``jax.lax.scan`` over T with the state
[heads, 128, 128] as its carry), its gradients from differentiating that
scan.

**Feed-forward**, every layer (lm_mla_step.py ``feed_forward``): ``s =
sigmoid(u W_r)`` [320]; the 8 largest of ``s + bias``; ``w_e = s_e / sum_S
s`` times ``routed_scaling_factor`` 1; ``y = sum_{e in S, e held} w_e E_e(u)
+ E_shared(u)``. The bias gets no gradient: after a step ``bias_e += gamma
sign(mean(load) - load_e)``, gamma ASSUMED 0.001.

Departures from the published model, each the configuration's
(benchmark/configs/solar-open2-250b-a15b-l4.json) and the program's alike:
- **the share**: the tensors are the held heads' and experts' (the delta
  heads ``first .. first + held - 1`` of 64: ``W_q``, ``W_k``, ``W_v``,
  ``W_fb``, ``W_gb``, ``W_b``, the convolutions, ``A_log``, ``dt_bias`` by
  head, ``W_o`` by rows; as many query heads with the key-value heads they
  read: ``W_q``, ``W_g``, ``W_o`` by query head, ``W_k``, ``W_v`` by
  key-value head; experts ``first .. first + held - 1`` of the 320, ``w_e``
  over all eight; a slice of the vocabulary's rows): a head reads no other
  head, so a layer adds its heads' part of ``W_o``'s sum, and what the
  absent heads and experts would add is left out;
- the eight may be GIVEN (``chosen``), as in lm_step.py;
- every held expert is computed over every token and weighted by ``w_e``
  or by 0.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.lm_kda_step import (  # noqa: F401 - callers use them
    CONVS, PRECISION, adam, adam_rows, attention, bias_step, conv,
    feed_forward, head_loss, load_of, recurrence, rmsnorm, routing)


def delta_inputs(c, p, x):
    """``(q, k, v, g [T, heads, K], beta [T, heads])`` of the normed input."""
    t, heads, d = x.shape[0], c["kda_heads"], c["kda_dim"]
    q, k, v = (jax.nn.silu(conv(x @ p[w], p[cw])).reshape(t, heads, d)
               for w, cw in zip(("wq", "wk", "wv"), CONVS))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(p["a_log"])[None, :, None] * jax.nn.softplus(
        (x @ p["w_fa"]) @ p["w_fb"] + p["dt_bias"]).reshape(t, heads, d)
    return q, k, v, g, c["beta_scale"] * jax.nn.sigmoid(x @ p["w_beta"])


def delta_f(c, p, h):
    t = h.shape[0]
    x = rmsnorm(h, p["norm_attn"], c["eps"])
    o = recurrence(*delta_inputs(c, p, x))
    gate = jax.nn.sigmoid((x @ p["w_ga"]) @ p["w_gb"])
    return (rmsnorm(o, p["norm_o"], c["eps"]).reshape(t, -1) * gate) @ p["wo"]


def softmax_f(c, p, h):
    t, heads, kv, d = h.shape[0], c["heads"], c["kv_heads"], c["head_dim"]
    x = rmsnorm(h, p["norm_attn"], c["eps"])
    q = (x @ p["wq"]).reshape(t, heads, d)
    # query head i reads key-value head i // (heads / kv)
    k, v = (jnp.repeat((x @ p[w]).reshape(t, kv, d), heads // kv, axis=1)
            for w in ("wk", "wv"))
    o = attention(q, k, v, d ** -0.5)
    gate = jax.nn.sigmoid(x @ p["w_attn_gate"])
    if gate.shape[1] == heads:      # the other reading: a gate a head
        gate = jnp.repeat(gate, d, axis=1)
    return (o.reshape(t, -1) * gate) @ p["wo"]


def layer(c, p, x, chosen=None, own=False):
    """One sequence ``x`` [T, hidden] through one layer whose tensors ``p``
    are named and shaped as the server's tables: a layer with an ``a_log``
    is a delta layer, one without a softmax one. With ``own`` also the
    experts this file would choose itself ([T, k]), whatever ``chosen``
    says."""
    a = x + (delta_f if "a_log" in p else softmax_f)(c, p, x)
    y = a + feed_forward(c, p, a, chosen)
    if not own:
        return y
    return y, routing(c, p["router"], p["router_bias"],
                      rmsnorm(a, p["norm_ffn"], c["eps"]))[0]


def step_loss(c, params, tokens, chosen=None):
    """The whole step's loss for ``tokens`` [B, T+1]: for ``jax.grad`` at
    small sizes. ``params`` is ``{"embedding", "layers": [..],
    "final_norm", "head"}``; ``chosen`` per layer [B, T, k] or None."""
    ids, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embedding"][ids]
    for i, p in enumerate(params["layers"]):
        if chosen is None:
            x = jax.vmap(lambda seq, p=p: layer(c, p, seq))(x)
        else:
            x = jax.vmap(lambda seq, ids, p=p: layer(c, p, seq, ids))(
                x, chosen[i])
    return head_loss(c, params["head"], params["final_norm"],
                     x.reshape(-1, x.shape[-1]), targets.reshape(-1),
                     targets.size)


def kinds(config: dict):
    """Each held layer's kind of attention: ``"gqa"`` | ``"kda"``."""
    full = set(config["gqa_layers"])
    return ["gqa" if i in full else "kda"
            for i in range(int(config["num_hidden_layers"]))]


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's keys (the
    published ``config.json``'s; the heads and experts are the HELD
    ones)."""
    linear = config["linear_attn_config"]
    return {
        "hidden": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "kda_heads": int(linear["num_heads"]),
        "kda_dim": int(linear["head_dim"]),
        "kda_conv": int(linear["short_conv_kernel_size"]),
        "beta_scale": 2.0 if config["kda_allow_neg_eigval"] else 1.0,
        "outputs": int(config["router_outputs"]),
        "top_k": int(config["num_experts_per_tok"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "held": int(config["n_routed_experts"]),
        "first_held": int(config.get("first_expert_held", 0)),
        "routed_scale": float(config["routed_scaling_factor"]),
        "bias_rate": float(config["router_bias_rate"]),
        "layers": int(config["num_hidden_layers"]),
        "eps": float(config["rms_norm_eps"])}

"""Plain reference for one training step of one chip's share of
GLM-4.7-Flash (zai-org 2026,
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json,
``model_type: glm4_moe_lite``: DeepSeek-V3's block on the plain residual,
rotary latent attention without YaRN, a multi-token module): the forward
pass, both losses, their gradients by ``jax.grad``/``jax.vjp``, Adam and the
router bias's step, in float32 ``jax.numpy`` at
``default_matmul_precision("highest")`` (callers set it: ``with
PRECISION:``), with no kernel, no sorting of tokens by expert, no bfloat16
and nothing imported from the program (``multiverso_tpu/models/lm``). Adam
and the norm are lm_step.py's, which the references share; the rotary turn,
the softmax and the routing are this file's own.

**A layer** (``layer``), hidden C = 2048, on the plain residual:

    a = x + Attn(RMSNorm(x; g_attn))
    y = a + F(RMSNorm(a; g_ffn))

**Attention** (``attention_f``), heads h of 20, ``u' = RMSNorm(x; g_attn)``:

    c_q           = RMSNorm(u' W_qa; g_qa)                 [q_lora_rank 768]
    [q_n | q_r]_h = c_q W_qb                               [192 | 64]
    [c_kv | k_r]  = u' W_kva                               [512 | 64]
    [k_n | v]_h   = RMSNorm(c_kv; g_kva) W_kvb             [192 | 256]
    q_r, k_r rotated by position at ``rope_theta``'s own frequencies
        (``rope_scaling`` null: theta^(-2i/64), theta 1e6; the halves
        paired), k_r one for all heads
    score = (q_n . k_n + q_r . k_r) 256^-0.5, causal
    Attn  = [softmax(score) v]_h side by side, W_o         [20 x 256, C]

**Feed-forward** (``feed_forward``), ``h = RMSNorm(a; g_ffn)``. Layers ``<
first_k_dense_replace``: ``W_d (silu(h W_g) * (h W_u))``, width 10240. The
others: ``s = sigmoid(h W_r)`` [64]; ``S`` = the 4 largest of ``s + bias``
(``n_group`` 1: no group limit); ``w_e = routed_scaling_factor s_e / sum_S
s`` (1.8); ``F = sum_{e in S, e held} w_e E_e(h) + E_shared(h)``, ``E``
silu-gated, routed width 1536, shared width ``n_shared_experts *
moe_intermediate_size`` = 1536. The bias gets no gradient: after a step
``bias_e += gamma sign(mean_e'(load) - load_e)``, load = the step's
assignments over all 64 outputs (``bias_step``), gamma ASSUMED 0.001
(DeepSeek-V3's, whose router ``noaux_tc`` is).

**Multi-token module** (``num_nextn_predict_layers`` 1; DeepSeek-V3's
report, section 2.2; ``mtp``): ``h'_i = W_p [RMSNorm(x_i; g_h) ;
RMSNorm(E[t_{i+1}]; g_e)]`` (``W_p`` [2C, C], ``x`` the last layer's output
before the final norm) through one sparse layer of the module's own, the
module's own final norm, the SAME head; it predicts ``t_{i+2}``. ``loss =
CE_main + lambda CE_mtp``, lambda ASSUMED 0.3. Embedding and head each get
the sum of their two gradients.

Departures from the published model, each the configuration's
(benchmark/configs/glm47-flash-30b-a3b-l5.json) and the program's alike:
- **the share**: experts ``first .. first + held - 1`` of the 64 (``w_e``
  over all four; what the absent experts would add is left out), a slice of
  the vocabulary's rows; attention, shared expert, dense MLP, router and
  norms are whole (heads: ``num_attention_heads`` of them, whose columns of
  ``W_qb``, ``W_kvb`` and rows of ``W_o`` the tensors hold);
- the four may be GIVEN (``chosen``), as in lm_step.py;
- every held expert is computed over every token and weighted by ``w_e``
  or by 0;
- the two norms before ``W_p`` carry weights (DeepSeek-V3's ``hnorm``,
  ``enorm``), ``[h ; e]`` in that order, and the rotary pairs are the
  halves (``rotate_half``).

Memory: ``attention`` goes a block of queries at a time and ``experts`` an
expert at a time, each under ``jax.checkpoint``; callers go a sequence and
a layer at a time.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference.lm_step import (  # noqa: F401 - callers use them
    PRECISION, adam, adam_rows, rmsnorm)


# -- attention ------------------------------------------------------------------

def frequencies(c):
    """The rotary pairs' own frequencies: no scaling."""
    d = c["rope_dim"]
    return c["rope_theta"] ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)


def rotary(x, inv):
    """[T, heads, d] turned by position at the frequencies ``inv``, the
    halves paired."""
    t, _, d = x.shape
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, scale, block=512):
    """q, k [T, heads, d], v [T, heads, dv] -> [T, heads, dv], causal; the
    softmax written out."""
    t, heads, d = q.shape
    block = min(block, t)
    assert t % block == 0

    @jax.checkpoint
    def one(args):
        qb, first = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        i = first + jnp.arange(block)[:, None]
        seen = jnp.arange(t)[None, :] <= i
        s = jnp.where(seen, s, -jnp.inf)
        e = jnp.where(seen, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
        return jnp.einsum("hqk,khd->qhd", e / jnp.sum(e, -1, keepdims=True),
                          v)

    out = jax.lax.map(one, (q.reshape(t // block, block, heads, d),
                            jnp.arange(0, t, block)))
    return out.reshape(t, heads, v.shape[-1])


def attention_f(c, p, u):
    t, heads = u.shape[0], c["heads"]
    nope, rope, latent = c["nope_dim"], c["rope_dim"], c["kv_rank"]
    h = rmsnorm(u, p["norm_attn"], c["eps"])
    c_q = rmsnorm(h @ p["wq_a"], p["norm_q_a"], c["eps"])
    q = (c_q @ p["wq_b"]).reshape(t, heads, nope + rope)
    kv_a = h @ p["wkv_a"]
    kv = (rmsnorm(kv_a[:, :latent], p["norm_kv_a"], c["eps"])
          @ p["wkv_b"]).reshape(t, heads, nope + c["v_dim"])
    inv = frequencies(c)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], inv)], -1)
    k_r = rotary(kv_a[:, None, latent:], inv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (t, heads, rope))], -1)
    o = attention(q, k, kv[..., nope:], 1.0 / math.sqrt(nope + rope))
    return o.reshape(t, -1) @ p["wo"]


# -- feed-forward -----------------------------------------------------------------

def routing(c, router, bias, h, chosen=None):
    """``(chosen [T, k], weights [T, outputs])``: every expert's weight
    for every token, zero outside the token's set of k."""
    s = jax.nn.sigmoid(h @ router)
    if chosen is None:
        chosen = jax.lax.top_k(s + bias, c["top_k"])[1]
    inside = jnp.any(chosen[:, :, None] == jnp.arange(c["outputs"]), axis=1)
    kept = jnp.where(inside, s, 0.0)
    return chosen, c["routed_scale"] * kept / jnp.sum(kept, -1, keepdims=True)


def gated(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def experts(c, h, weights, w_gate, w_up, w_down):
    """The held experts' part of the sum: ``weights`` [T, held]."""
    held, hidden, width = c["held"], c["hidden"], c["expert_width"]

    @jax.checkpoint
    def one(acc, e):
        gate, up, down, w = e
        return acc + w[:, None] * gated(h, gate, up, down), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        w_gate.reshape(held, hidden, width), w_up.reshape(held, hidden, width),
        w_down.reshape(held, width, hidden), weights.T))
    return acc


def feed_forward(c, p, u, chosen=None):
    h = rmsnorm(u, p["norm_ffn"], c["eps"])
    if "router" not in p:
        return gated(h, p["w_gate"], p["w_up"], p["w_down"])
    _, weights = routing(c, p["router"], p["router_bias"], h, chosen)
    first = c["first_held"]
    y = experts(c, h, weights[:, first:first + c["held"]], p["w_gate"],
                p["w_up"], p["w_down"])
    return y + gated(h, p["ws_gate"], p["ws_up"], p["ws_down"])


def layer(c, p, x, chosen=None, own=False):
    """One sequence ``x`` [T, C] through one layer whose tensors ``p`` are
    named and shaped as the server's tables (a layer with a ``router`` is
    sparse, one without dense). With ``own`` also the experts this file
    would choose itself ([T, k]; None in a dense layer), whatever ``chosen``
    says."""
    a = x + attention_f(c, p, x)
    y = a + feed_forward(c, p, a, chosen)
    if not own:
        return y
    ids = routing(c, p["router"], p["router_bias"],
                  rmsnorm(a, p["norm_ffn"], c["eps"]))[0] \
        if "router" in p else None
    return y, ids


def projected(c, p, xs, e_next):
    """The module's layer's input: ``W_p`` of both norms, ``[h ; e]``."""
    return jnp.concatenate([rmsnorm(xs, p["norm_h"], c["eps"]),
                            rmsnorm(e_next, p["norm_e"], c["eps"])],
                           -1) @ p["proj"]


def mtp(c, p, xs, e_next, chosen=None, own=False):
    """The module for one sequence: the last layer's output ``xs`` [T, C]
    and the next tokens' embedding rows -> [T, C] before the module's final
    norm. ``p`` holds ``proj``, ``norm_h``, ``norm_e`` and the layer's
    tensors."""
    return layer(c, p, projected(c, p, xs, e_next), chosen, own)


def head_loss(c, head, norm, x, targets, total):
    """Sum of the cross entropy of ``targets`` over ``x`` [N, hidden], over
    ``total`` (the mean's denominator)."""
    logits = rmsnorm(x, norm, c["eps"]) @ head.T
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked) / total


def load_of(c, chosen):
    """A layer's assignments a router output over the step: ``chosen``
    [B, T, k] -> [outputs]."""
    return jnp.sum(chosen.reshape(-1, 1) == jnp.arange(c["outputs"]), axis=0)


def bias_step(c, bias, load):
    """The bias after a step that saw ``load``."""
    load = load.astype(jnp.float32)
    return bias + c["bias_rate"] * jnp.sign(jnp.mean(load) - load)


def step_loss(c, params, tokens, chosen=None):
    """The whole step's loss for ``tokens`` [B, T+2]: for ``jax.grad`` at
    small sizes. ``params`` is ``{"embedding", "layers": [..],
    "final_norm", "head"}`` and with a module ``"mtp"`` (its tensors and
    ``final_norm``); ``chosen`` ``{"layers": [per layer [B, T, k] or
    None], "mtp": [B, T, k]}`` or None. Returns ``(loss, (main, second))``."""
    t = tokens.shape[1] - 2
    ids, first, second = tokens[:, :t], tokens[:, 1:t + 1], tokens[:, 2:]
    pick = (lambda i, b: None) if chosen is None else (
        lambda i, b: None if chosen["layers"][i] is None
        else chosen["layers"][i][b])
    x = params["embedding"][ids]
    for i, p in enumerate(params["layers"]):
        x = jnp.stack([layer(c, p, x[b], pick(i, b))
                       for b in range(x.shape[0])])
    main = head_loss(c, params["head"], params["final_norm"],
                     x.reshape(-1, x.shape[-1]), first.reshape(-1),
                     first.size)
    if "mtp" not in params:
        return main, (main, 0.0)
    m = params["mtp"]
    y = jnp.stack([
        mtp(c, m, x[b], params["embedding"][first[b]],
            None if chosen is None else chosen["mtp"][b])
        for b in range(x.shape[0])])
    extra = head_loss(c, params["head"], m["final_norm"],
                      y.reshape(-1, y.shape[-1]), second.reshape(-1),
                      second.size)
    return main + c["mtp_weight"] * extra, (main, extra)


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's keys (the
    published ``config.json``'s)."""
    assert config.get("rope_scaling") is None, "plain rotary positions alone"
    return {
        "hidden": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope_dim": int(config["qk_nope_head_dim"]),
        "rope_dim": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "rope_theta": float(config["rope_theta"]),
        "outputs": int(config["router_outputs"]),
        "top_k": int(config["num_experts_per_tok"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "held": int(config["n_routed_experts"]),
        "first_held": int(config.get("first_expert_held", 0)),
        "routed_scale": float(config["routed_scaling_factor"]),
        "bias_rate": float(config["router_bias_rate"]),
        "layers": int(config["num_hidden_layers"]),
        "dense_layers": int(config["first_k_dense_replace"]),
        "mtp_layers": int(config["num_nextn_predict_layers"]),
        "mtp_weight": float(config["mtp_loss_weight"]),
        "eps": float(config["rms_norm_eps"])}

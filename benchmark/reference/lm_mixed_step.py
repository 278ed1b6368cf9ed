"""Plain reference for one training step of one chip's share of
Laguna-XS.2 (poolside 2026, 33.4B-A3B,
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json,
``model_type: laguna``): the forward pass, the loss, its gradients by
``jax.grad``/``jax.vjp`` and Adam, in float32 ``jax.numpy`` at
``default_matmul_precision("highest")`` (callers set it: ``with
PRECISION:``), with no kernel, no sorting of tokens by expert, no bfloat16
and nothing imported from the program (``multiverso_tpu/models/lm``). Adam
and the norm are lm_step.py's, which the references share.

**The layer** (``layer``), each line by its config key; ``h =
RMSNorm(x)`` with ``rms_norm_eps`` 1e-6 throughout.

*Attention*, layer ``l`` of kind ``layer_types[l]`` with ``H_l =
num_attention_heads_per_layer[l]`` query heads (48 on ``full_attention``
layers, 64 on ``sliding_attention`` ones), ``num_key_value_heads`` 8,
``head_dim`` d = 128:

    q = h W_q  [H_l, d];  k = h W_k,  v = h W_v  [8, d]
    query head i reads key-value head i // (H_l / 8)       (6 | 8 a group)
    sliding layers (``rope_parameters.sliding_attention``: default,
        rope_theta 10000, partial_rotary_factor 1): all 128 lanes of q and
        k rotated, inv_freq_j = 10000^(-2j/128)
    full layers (``rope_parameters.full_attention``: yarn, rope_theta
        500000, factor 64, original_max_position_embeddings 4096,
        beta_fast 64, beta_slow 1, attention_factor 1.4158883,
        partial_rotary_factor 0.5): the FIRST 64 lanes of each head
        rotated, the other 64 as they are; inv_freq_j = 500000^(-2j/64),
        blended between itself and itself / 64 by the linear ramp between
        the correction dimensions of beta_fast and beta_slow at 4096
        positions (``yarn_frequencies``); cos and sin times
        attention_factor (= 0.1 ln 64 + 1), so the rotated part of a score
        carries its square and the unrotated part does not
    halves paired (``rotate_half``)
    score = q . k * 128^-0.5; full layers causal, sliding layers causal
        over the last ``sliding_window`` 512 positions (j > i - 512)
    o_i = softmax(score_i) v
    gate (``gating: true``): g = sigmoid(h W_g), W_g [2048, H_l];
        o_i <- g_i o_i
    a = x + concat(o) W_o

*Feed-forward* on ``h = RMSNorm(a)``. ``mlp_layer_types[l] == "dense"``
(layer 0): ``W_d (silu(h W_g) * (h W_u))``, ``intermediate_size`` 8192.
``"sparse"``: ``s = sigmoid(h W_r)`` [256] float32; ``S`` = the
``num_experts_per_tok`` 8 largest of ``s``; ``w_e =
moe_routed_scaling_factor s_e / sum_S s`` (2.5); ``y = a + sum_{e in S, e
held} w_e E_e(h) + E_shared(h)``, ``E`` silu-gated, width
``moe_intermediate_size`` 512 and ``shared_expert_intermediate_size`` 512;
the weights on the experts' OUTPUTS (``moe_apply_router_weight_on_input:
false``).

Then the final norm, the untied head over the vocabulary slice and the
mean cross entropy of the next token (``head_loss``).

**Assumed** (the configuration's ``assumed`` has each with its reason):
(1) ``gating: true`` is a per-head sigmoid gate on the attention's output,
from the normed layer input: with every other tensor as the config gives
it the whole model counts 33.44B parameters with a ``[2048, H_l]`` gate
and 34.07B with an elementwise ``[2048, H_l x 128]`` one, and the model is
described as 33.4B; the other reading the count allows, one scalar gate on
the shared expert's output, is not taken. (2) No q/k norms, no attention
bias. (3) ``hidden_act`` silu. (4) The router: sigmoid scores, no
correction bias, normalised over the chosen eight, on the normed
post-attention stream. (5) YaRN's factor on cos and sin, the rotated lanes
first. (6) Optimizer and initialisation as the three older configurations.

Departures from the published model, each the configuration's
(benchmark/configs/laguna-xs2-33b-a3b-l5.json) and the program's alike:
- **the share**: experts ``first .. first + held - 1`` of the 256
  (``w_e`` over all eight), a slice of the vocabulary's rows; what the
  absent experts would add is left out; attention, gate, router, shared
  expert, dense MLP and norms are whole;
- the eight may be GIVEN (``chosen``), as in lm_step.py;
- every held expert is computed over every token and weighted by ``w_e``
  or by 0.

Memory: ``attention`` goes a block of queries at a time (the mask a
dense predicate over the block) and ``experts`` an expert at a time, each
under ``jax.checkpoint``; callers go a sequence and a layer at a time.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference.lm_step import (  # noqa: F401 - callers use them
    PRECISION, adam, adam_rows, head_loss, rmsnorm)


def yarn_frequencies(r):
    """The rotated pairs' frequencies of a rotary kind ``r`` [lanes / 2]."""
    d, theta = r["lanes"], r["theta"]
    own = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if not r["yarn"]:
        return own

    def pair_of(turns):
        return d * math.log(r["original"] / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(r["beta_fast"])), 0)
    high = min(math.ceil(pair_of(r["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return own / r["factor"] * ramp + own * (1 - ramp)


def rotary(x, r):
    """[T, heads, d]: the first ``lanes`` lanes turned by position at the
    kind's frequencies (the halves of those lanes paired), cos and sin
    times its attention factor; the other lanes as they are."""
    t, lanes = x.shape[0], r["lanes"]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * yarn_frequencies(r)[None, :]
    cos = r["attention_factor"] * jnp.cos(angle)[:, None, :]
    sin = r["attention_factor"] * jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :lanes // 2], x[..., lanes // 2:lanes]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., lanes:]], -1)


def attention(q, k, v, window, block=256):
    """q [T, heads, d], k and v [T, kv heads, d] -> [T, heads, d]; causal,
    and with a ``window`` over the last ``window`` positions alone. (A
    block of 256 queries: 64 heads' scores against 8192 keys are 0.5 GB
    in float32, and their backward pass holds three such.)"""
    t, heads, d = q.shape
    per = heads // k.shape[1]
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
    block = min(block, t)
    assert t % block == 0

    @jax.checkpoint
    def one(args):
        qb, first = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        i = first + jnp.arange(block)[:, None]
        j = jnp.arange(t)[None, :]
        seen = j <= i
        if window:
            seen = seen & (j > i - window)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(one, (q.reshape(t // block, block, heads, d),
                            jnp.arange(0, t, block)))
    return out.reshape(t, heads, d)


def attention_block(c, windowed, p, x):
    """``x + Attn(RMSNorm(x))`` for a layer of the kind ``windowed``."""
    t, d = x.shape[0], c["head_dim"]
    r = c["rotary"][bool(windowed)]
    h = rmsnorm(x, p["norm_attn"], c["eps"])
    heads = p["wq"].shape[1] // d       # the layer's own: 48 | 64
    q = rotary((h @ p["wq"]).reshape(t, heads, d), r)
    k = rotary((h @ p["wk"]).reshape(t, c["kv_heads"], d), r)
    v = (h @ p["wv"]).reshape(t, c["kv_heads"], d)
    o = attention(q, k, v, c["window"] if windowed else 0)
    gate = jax.nn.sigmoid(h @ p["w_attn_gate"])         # [T, heads]
    return x + (o * gate[:, :, None]).reshape(t, -1) @ p["wo"]


def routing(c, router, h, chosen=None):
    """``(chosen [T, k], weights [T, outputs])``: every expert's weight
    for every token, zero outside the token's set of k."""
    s = jax.nn.sigmoid(h @ router)
    if chosen is None:
        chosen = jax.lax.top_k(s, c["top_k"])[1]
    inside = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(True)
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


def feed_forward(c, p, a, chosen=None, shared=True):
    """``F(RMSNorm(a))``: a layer with a ``router`` is sparse, one without
    dense. ``shared`` False leaves the shared expert out (the share test
    counts it once)."""
    h = rmsnorm(a, p["norm_ffn"], c["eps"])
    if "router" not in p:
        return gated(h, p["w_gate"], p["w_up"], p["w_down"])
    _, weights = routing(c, p["router"], h, chosen)
    first = c["first_held"]
    y = experts(c, h, weights[:, first:first + c["held"]], p["w_gate"],
                p["w_up"], p["w_down"])
    return y + gated(h, p["ws_gate"], p["ws_up"], p["ws_down"]) if shared \
        else y


def layer(c, windowed, p, x, chosen=None, own=False):
    """One sequence ``x`` [T, hidden] through one layer whose tensors
    ``p`` are named and shaped as the server's tables. With ``own`` also
    the experts this file would choose itself ([T, k]; None in a dense
    layer), whatever ``chosen`` says."""
    a = attention_block(c, windowed, p, x)
    y = a + feed_forward(c, p, a, chosen)
    if not own:
        return y
    return y, (routing(c, p["router"], rmsnorm(a, p["norm_ffn"], c["eps"]))[0]
               if "router" in p else None)


def step_loss(c, params, tokens, chosen=None):
    """The whole step's loss for ``tokens`` [B, T+1]: for ``jax.grad`` at
    small sizes. ``params`` is ``{"embedding", "layers": [..],
    "final_norm", "head"}``; ``chosen`` per layer [B, T, k] or None."""
    ids, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embedding"][ids]
    for i, p in enumerate(params["layers"]):
        given = None if chosen is None else chosen[i]
        x = jnp.stack([
            layer(c, c["window_layout"][i], p, x[b],
                  None if given is None else given[b])
            for b in range(x.shape[0])])
    return head_loss(c, params["head"], params["final_norm"],
                     x.reshape(-1, x.shape[-1]), targets.reshape(-1),
                     targets.size)


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's keys (the
    published ``config.json``'s)."""
    n, d = int(config["num_hidden_layers"]), int(config["head_dim"])

    def kind(p):
        yarn = p["rope_type"] == "yarn"
        return {"theta": float(p["rope_theta"]),
                "lanes": int(d * float(p.get("partial_rotary_factor", 1))),
                "yarn": yarn,
                "factor": float(p.get("factor", 1)),
                "beta_fast": float(p.get("beta_fast", 0)),
                "beta_slow": float(p.get("beta_slow", 0)),
                "original": float(p.get("original_max_position_embeddings",
                                        0)),
                "attention_factor": float(p["attention_factor"])
                if yarn else 1.0}

    rope = config["rope_parameters"]
    return {
        "hidden": int(config["hidden_size"]),
        "kv_heads": int(config["num_key_value_heads"]), "head_dim": d,
        "heads_layout": list(config["num_attention_heads_per_layer"][:n]),
        "window_layout": [int(t == "sliding_attention")
                          for t in config["layer_types"][:n]],
        "window": int(config["sliding_window"]),
        # by kind: [full_attention's, sliding_attention's]
        "rotary": [kind(rope["full_attention"]),
                   kind(rope["sliding_attention"])],
        "outputs": int(config["router_outputs"]),
        "top_k": int(config["num_experts_per_tok"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "held": int(config["num_experts"]),
        "first_held": int(config.get("first_expert_held", 0)),
        "routed_scale": float(config["moe_routed_scaling_factor"]),
        "eps": float(config["rms_norm_eps"])}

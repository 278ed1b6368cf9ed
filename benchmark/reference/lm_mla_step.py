"""Plain reference for one training step of one chip's share of
Xing4.0-29B-A4B (XingChen-AGI 2026,
https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B, ``model_type:
xing4_0``: DeepSeek-V3's block with a four-stream constrained residual):
the forward pass, both losses, their gradients by ``jax.grad``/``jax.vjp``,
Adam and the router bias's step, in float32 ``jax.numpy`` at
``default_matmul_precision("highest")`` (callers set it: ``with
PRECISION:``), with no kernel, no sorting of tokens by expert, no bfloat16
and nothing imported from the program (``multiverso_tpu/models/lm``). Adam
and the norm are lm_step.py's, which the references share.

**Streams** (``hc_mult`` n = 4, hidden C = 3584; the form is the mHC
paper's, arXiv 2512.24880; n, the iterations, the eps and the clamp the
config's). ``X`` is [T, n C], stream ``j`` the columns ``j C .. (j+1) C -
1``; ``X_0``'s streams are all ``E[ids]``. A sublayer ``F`` with its own
``phi`` [2n + n^2, n C], ``b`` [2n + n^2] and scalars ``a = (a_pre,
a_post, a_res)`` (``sublayer``):

    r         = RMSNorm(X) over all n C, no weight
    [p, q, R] = r phi^T
    H_pre     = sigmoid(a_pre p + b_pre)                      [T, n]
    H_post    = 2 sigmoid(a_post q + b_post)                  [T, n]
    H_res     = SK(clamp(a_res R + b_res, mhc_h_res_clamp_min, _max))
                SK = exp, then hc_sinkhorn_iters = 20 rounds of: each row
                over (its sum + hc_eps), each column over (its sum + hc_eps)
    u         = sum_j H_pre[:, j] X_j
    v         = F(RMSNorm(u; g))
    X'_i      = sum_j H_res[:, i, j] X_j + H_post[:, i] v

After the last layer ``x = sum_j X_j`` (ASSUMED, as hyper-connections do),
the final norm, the head.

**Attention** (``F`` of every layer; ``attention_f``), heads h of 32:

    c_q           = RMSNorm(u' W_qa; g_qa)                 [q_lora_rank 768]
    [q_n | q_r]_h = c_q W_qb                               [128 | 64]
    [c_kv | k_r]  = u' W_kva                               [512 | 64]
    [k_n | v]_h   = RMSNorm(c_kv; g_kva) W_kvb             [128 | 128]
    q_r, k_r rotated (the halves paired; YaRN's frequencies: rope_theta
        10000, factor 64, beta_fast 32, beta_slow 1, original 4096), k_r
        one for all heads
    score = (q_n . k_n + q_r . k_r) 192^-0.5 m^2,  m = 0.1 mscale_all_dim
        ln(64) + 1; causal;  F = [softmax(score) v]_h side by side, W_o

(``u' = RMSNorm(u; g_attn)``.)

**Feed-forward** (``F``). Layers ``< first_k_dense_replace``: ``W_d
(silu(h W_g) * (h W_u))``, width 9216. The others: ``s = sigmoid(h W_r)``
[64]; ``S`` = the 4 largest of ``s + bias`` (``n_group`` 1: no group
limit); ``w_e = routed_scaling_factor s_e / sum_S s``; ``y = sum_{e in S,
e held} w_e E_e(h) + E_shared(h)``, ``E`` silu-gated, routed width 1024,
shared width ``n_shared_experts * moe_intermediate_size`` = 1024. The bias
gets no gradient: after a step ``bias_e += gamma sign(mean_e'(load) -
load_e)``, load = the step's assignments over all 64 outputs
(``bias_step``), gamma ASSUMED 0.001 (DeepSeek-V3's, whose router this is).

**Multi-token module** (``num_nextn_predict_layers`` 1; DeepSeek-V3's
report, section 2.2; ``mtp``): ``h'_i = W_p [RMSNorm(x_i; g_h) ;
RMSNorm(E[t_{i+1}]; g_e)]`` (``W_p`` [2C, C], ``x`` the summed streams
before the final norm), ``h'`` in every stream of one sparse layer, summed,
the module's own final norm, the SAME head; it predicts ``t_{i+2}``.
``loss = CE_main + lambda CE_mtp``, lambda ASSUMED 0.3. Embedding and head
each get the sum of their two gradients.

Departures from the published model, each the configuration's
(benchmark/configs/xing4-29b-a4b-l5.json) and the program's alike:
- **the share**: heads ``first .. first + held - 1`` (``W_qb``, ``W_kvb``,
  ``W_o`` cut by head, the layer adds its heads' part of ``W_o``'s sum),
  experts ``first .. first + held - 1`` of the 64 (``w_e`` over all four),
  a slice of the vocabulary's rows; what the absent heads and experts
  would add is left out;
- the four may be GIVEN (``chosen``), as in lm_step.py;
- every held expert is computed over every token and weighted by ``w_e``
  or by 0;
- the two norms before ``W_p`` carry weights (DeepSeek-V3's ``hnorm``,
  ``enorm``), and the rotary pairs are the halves (``rotate_half``).

Memory: ``attention`` goes a block of queries at a time and ``experts`` an
expert at a time, each under ``jax.checkpoint``; callers go a sequence and
a layer at a time.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.reference.lm_step import (  # noqa: F401 - callers use them
    PRECISION, adam, adam_rows, rmsnorm)

MIXER = ("phi", "b", "a")


# -- streams ------------------------------------------------------------------

def sinkhorn(logits, iters, eps):
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def sublayer(c, phi, b, a, x, f):
    """``X -> X'`` around ``f``: [T, n C] -> [T, n C]."""
    n, t = c["hc_mult"], x.shape[0]
    streams = x.reshape(t, n, c["hidden"])
    r = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + c["eps"])
    raw = r @ phi.T
    pre = jax.nn.sigmoid(a[0] * raw[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * raw[:, n:2 * n] + b[n:2 * n])
    res = sinkhorn(jnp.clip(a[2] * raw[:, 2 * n:] + b[2 * n:],
                            c["clamp_min"], c["clamp_max"]).reshape(t, n, n),
                   c["hc_iters"], c["hc_eps"])
    v = f(jnp.einsum("tj,tjc->tc", pre, streams))
    out = jnp.einsum("tij,tjc->tic", res, streams) \
        + post[:, :, None] * v[:, None, :]
    return out.reshape(t, n * c["hidden"])


# -- attention ------------------------------------------------------------------

def yarn_frequencies(c):
    d, theta = c["rope_dim"], c["rope_theta"]
    own = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def pair_of(turns):
        return d * math.log(c["yarn_original"] / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(c["yarn_beta_fast"])), 0)
    high = min(math.ceil(pair_of(c["yarn_beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return own / c["yarn_factor"] * ramp + own * (1 - ramp)


def rotary(x, inv):
    """[T, heads, d] turned by position at the frequencies ``inv``, the
    halves paired."""
    t, _, d = x.shape
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, scale, block=1024):
    """q, k [T, heads, d], v [T, heads, dv] -> [T, heads, dv], causal."""
    t, heads, d = q.shape
    block = min(block, t)
    assert t % block == 0

    @jax.checkpoint
    def one(args):
        qb, first = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        i = first + jnp.arange(block)[:, None]
        j = jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

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
    inv = yarn_frequencies(c)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], inv)], -1)
    k_r = rotary(kv_a[:, None, latent:], inv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (t, heads, rope))], -1)
    m = 0.1 * c["yarn_mscale_all_dim"] * math.log(c["yarn_factor"]) + 1.0
    scale = m * m / math.sqrt(nope + rope)
    o = attention(q, k, kv[..., nope:], scale)
    return o.reshape(t, -1) @ p["wo"]


# -- feed-forward -----------------------------------------------------------------

def routing(c, router, bias, h, chosen=None):
    """``(chosen [T, k], weights [T, outputs])``: every expert's weight
    for every token, zero outside the token's set of k."""
    s = jax.nn.sigmoid(h @ router)
    if chosen is None:
        chosen = jax.lax.top_k(s + bias, c["top_k"])[1]
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
    """One sequence's streams ``x`` [T, n C] through one layer whose
    tensors ``p`` are named and shaped as the server's tables (a layer
    with a ``router`` is sparse, one without dense). With ``own`` also the
    experts this file would choose itself ([T, k]; None in a dense layer),
    whatever ``chosen`` says."""
    a = sublayer(c, *(p[f"hc_attn_{k}"] for k in MIXER), x,
                 lambda u: attention_f(c, p, u))
    seen = {}

    def f(u):
        if own and "router" in p:
            seen["ids"] = routing(c, p["router"], p["router_bias"],
                                  rmsnorm(u, p["norm_ffn"], c["eps"]))[0]
        return feed_forward(c, p, u, chosen)

    y = sublayer(c, *(p[f"hc_ffn_{k}"] for k in MIXER), a, f)
    return (y, seen.get("ids")) if own else y


def expand(c, h):
    return jnp.tile(h, c["hc_mult"])


def collapse(c, x):
    return x.reshape(x.shape[:-1] + (c["hc_mult"], c["hidden"])).sum(-2)


def mtp(c, p, xs, e_next, chosen=None):
    """The module for one sequence: summed streams ``xs`` [T, C] and the
    next tokens' embedding rows -> [T, C] before the module's final norm.
    ``p`` holds ``proj``, ``norm_h``, ``norm_e`` and the layer's tensors."""
    both = jnp.concatenate([rmsnorm(xs, p["norm_h"], c["eps"]),
                            rmsnorm(e_next, p["norm_e"], c["eps"])], -1)
    return collapse(c, layer(c, p, expand(c, both @ p["proj"]), chosen))


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
    x = expand(c, params["embedding"][ids])
    for i, p in enumerate(params["layers"]):
        x = jnp.stack([layer(c, p, x[b], pick(i, b))
                       for b in range(x.shape[0])])
    xs = collapse(c, x)
    main = head_loss(c, params["head"], params["final_norm"],
                     xs.reshape(-1, xs.shape[-1]), first.reshape(-1),
                     first.size)
    if "mtp" not in params:
        return main, (main, 0.0)
    m = params["mtp"]
    y = jnp.stack([
        mtp(c, m, xs[b], params["embedding"][first[b]],
            None if chosen is None else chosen["mtp"][b])
        for b in range(xs.shape[0])])
    extra = head_loss(c, params["head"], m["final_norm"],
                      y.reshape(-1, y.shape[-1]), second.reshape(-1),
                      second.size)
    return main + c["mtp_weight"] * extra, (main, extra)


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's keys (the
    published ``config.json``'s)."""
    y = config["rope_scaling"]
    return {
        "hidden": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope_dim": int(config["qk_nope_head_dim"]),
        "rope_dim": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "rope_theta": float(config["rope_theta"]),
        "yarn_factor": float(y["factor"]),
        "yarn_beta_fast": float(y["beta_fast"]),
        "yarn_beta_slow": float(y["beta_slow"]),
        "yarn_original": float(y["original_max_position_embeddings"]),
        "yarn_mscale_all_dim": float(y["mscale_all_dim"]),
        "outputs": int(config["router_outputs"]),
        "top_k": int(config["num_experts_per_tok"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "held": int(config["n_routed_experts"]),
        "first_held": int(config.get("first_expert_held", 0)),
        "routed_scale": float(config["routed_scaling_factor"]),
        "bias_rate": float(config["router_bias_rate"]),
        "layers": int(config["num_hidden_layers"]),
        "dense_layers": int(config["first_k_dense_replace"]),
        "hc_mult": int(config["hc_mult"]),
        "hc_iters": int(config["hc_sinkhorn_iters"]),
        "hc_eps": float(config["hc_eps"]),
        "clamp_min": float(config["mhc_h_res_clamp_min"]),
        "clamp_max": float(config["mhc_h_res_clamp_max"]),
        "mtp_layers": int(config["num_nextn_predict_layers"]),
        "mtp_weight": float(config["mtp_loss_weight"]),
        "eps": float(config["rms_norm_eps"])}

"""Plain reference for one block-diffusion training step of one chip's
share of SDAR-30B-A3B-Chat (JetLM 2025,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat, ``model_type: sdar_moe``,
Qwen3-MoE's block): the forward pass over the noised and the clean copy
of each sequence side by side, the loss over the masked positions, its
gradients by ``jax.grad``/``jax.vjp`` and Adam, in float32 ``jax.numpy``
at ``default_matmul_precision("highest")`` (callers set it: ``with
PRECISION:``), with no kernel, no sorting of tokens by expert, no
bfloat16 and nothing imported from the program
(``multiverso_tpu/models/lm``). Adam, the norm and the router's weights
are lm_step.py's, which the two references share.

For a layer's input ``x`` [T, hidden] at positions ``pos`` [T] (``layer``):

    h  = RMSNorm(x; g_attn)
    q  = RMSNorm_head(h Wq; g_q)   k = RMSNorm_head(h Wk; g_k)   v = h Wv
         (32 / 4 / 4 heads of 128; g_q, g_k [128], each head normed alone)
    q, k = rotary(q, pos), rotary(k, pos)      (theta 1e6, halves paired)
    a  = x + softmax(q k^T / sqrt(128) + M) v Wo
         (query head i reads key-value head i // 8)
    u  = RMSNorm(a; g_ffn);  p = softmax(u W_r) over 128;  S = top-8 of p;
    w_e = p_e / sum_S p
    y  = a + sum_{e in S, e held} w_e W_d,e (silu(u W_g,e) * (u W_u,e))

then a final RMSNorm and logits over the head's rows.

The objective. A sequence of ``L`` clean tokens ``c`` is cut into ``L/b``
blocks; block ``j`` has a ``t_j`` and some of its positions masked; the
noised copy ``n`` has the mask token there and ``c_i`` elsewhere. The
input is ``[n ; c]``, ``T = 2L`` positions at ``pos = [0..L-1 ; 0..L-1]``.
With ``blk(i) = (i mod L) // b``, query ``i`` sees key ``j`` iff (``sees``)

    i noised, j noised:  blk(j) == blk(i)
    i noised, j clean :  blk(j) <  blk(i)
    i clean , j clean :  blk(j) <= blk(i)
    i clean , j noised:  never

The logits at noised position ``i`` predict ``c_i`` (no shift), and
``loss = 1/(B L) sum over masked i of (1 / t_blk(i)) CE(logits_i, c_i)``.

The noise is the TRAFFIC: this file is given each step's noised ids,
masked flags and ``t`` and checks what it is given (``check_noise``:
``n == c`` off the mask, the mask token on it, no clean mask token, ``t``
in range); the weights ``1/t`` are its own.

Departures from the published model, each the configuration's
(benchmark/configs/sdar-30b-a3b-l6.json) and the program's alike: the
share (experts ``first .. first + held - 1`` of the 128, ``w_e`` still
normalised over all eight; a slice of the vocabulary's rows, the mask
token its last); the eight may be GIVEN (``chosen``), as in lm_step.py;
every held expert is computed over every token and weighted by ``w_e`` or
by 0.

Memory: ``attention`` goes a block of queries at a time under
``jax.checkpoint`` (the mask a dense predicate over the block's rows and
every key) and ``experts`` an expert at a time, so that a layer's
gradient at 8192 positions fits beside the tables; callers go a sequence
and a layer at a time.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.lm_step import (  # noqa: F401 - callers use them
    PRECISION, adam, adam_rows, rmsnorm, routing)


def rotary(x, pos, theta):
    """[T, heads, d] turned by each row's position, the halves paired."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sees(i, j, half, block):
    """Whether query ``i`` sees key ``j`` among ``2 * half`` positions,
    the noised copy first: the four cases of the module's docstring."""
    i_clean, j_clean = i >= half, j >= half
    bi, bj = (i % half) // block, (j % half) // block
    return jnp.where(
        i_clean, j_clean & (bj <= bi),
        jnp.where(j_clean, bj < bi, bj == bi))


def attention(q, k, v, half, block, rows=512):
    """q [T, heads, d], k and v [T, kv heads, d] -> [T, heads, d] under
    the block-diffusion mask, ``T = 2 half``."""
    t, heads, d = q.shape
    per = heads // k.shape[1]
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
    rows = min(rows, t)
    assert t == 2 * half and t % rows == 0

    @jax.checkpoint
    def one(args):
        qb, first = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(d))
        i = first + jnp.arange(rows)[:, None]
        j = jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(sees(i, j, half, block), s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(one, (q.reshape(t // rows, rows, heads, d),
                            jnp.arange(0, t, rows)))
    return out.reshape(t, heads, d)


def experts(c, u, weights, w_gate, w_up, w_down):
    """The held experts' part of the sum: ``weights`` [T, held]."""
    held, hidden, width = c["held"], c["hidden"], c["expert_width"]

    @jax.checkpoint
    def one(acc, e):
        gate, up, down, w = e
        return acc + w[:, None] * ((jax.nn.silu(u @ gate) * (u @ up))
                                   @ down), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        w_gate.reshape(held, hidden, width), w_up.reshape(held, hidden, width),
        w_down.reshape(held, width, hidden), weights.T))
    return acc


def positions(half):
    return jnp.tile(jnp.arange(half), 2)


def layer(c, p, x, chosen=None, own=False):
    """One sequence's two copies ``x`` [2L, hidden] through one layer
    whose tensors ``p`` are named and shaped as the server's tables.
    With ``own`` also the experts this file would choose itself
    ([2L, k]), whatever ``chosen`` says."""
    t, eps = x.shape[0], c["eps"]
    half, pos = t // 2, positions(t // 2)
    h = rmsnorm(x, p["norm_attn"], eps)
    q = rmsnorm((h @ p["wq"]).reshape(t, c["heads"], c["head_dim"]),
                p["norm_q"], eps)
    k = rmsnorm((h @ p["wk"]).reshape(t, c["kv_heads"], c["head_dim"]),
                p["norm_k"], eps)
    v = (h @ p["wv"]).reshape(t, c["kv_heads"], c["head_dim"])
    q, k = rotary(q, pos, c["rope_theta"]), rotary(k, pos, c["rope_theta"])
    a = x + attention(q, k, v, half, c["block_length"]).reshape(t, -1) \
        @ p["wo"]
    u = rmsnorm(a, p["norm_ffn"], eps)
    _, weights = routing(c, p["router"], u, chosen)
    first = c["first_held"]
    y = a + experts(c, u, weights[:, first:first + c["held"]],
                    p["w_gate"], p["w_up"], p["w_down"])
    return (y, routing(c, p["router"], u)[0]) if own else y


def loss_weights(c, masked, t):
    """``1 / t`` of its block at a masked position, 0 elsewhere:
    [B, L] from ``masked`` [B, L] and ``t`` [B, L / b]."""
    return jnp.where(masked, 1.0 / jnp.repeat(t, c["block_length"], axis=1),
                     0.0)


def head_loss(c, head, norm, x, targets, weights, total):
    """The weighted cross entropy of ``targets`` over ``x`` [N, hidden]
    (noised positions), summed, over ``total`` (the step's clean tokens:
    the mean's denominator)."""
    logits = rmsnorm(x, norm, c["eps"]) @ head.T
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(weights * (jax.nn.logsumexp(logits, axis=-1) - picked)) \
        / total


def check_noise(c, clean, noised, masked, t) -> list:
    """What is wrong with the noise it was given (arrays on any device;
    [B, L], [B, L], [B, L] bool, [B, L / b])."""
    wrong = []
    mask_id = c["mask_id"]
    if bool(jnp.any(clean == mask_id)):
        wrong.append("a clean token is the mask token")
    if bool(jnp.any(jnp.where(masked, noised != mask_id, noised != clean))):
        wrong.append("the noised copy is not the mask token on the mask "
                     "and the clean token off it")
    if not bool(jnp.all((t > c["t_min"]) & (t <= 1.0))):
        wrong.append("a block's t outside (t_min, 1]")
    if t.shape != (clean.shape[0], clean.shape[1] // c["block_length"]):
        wrong.append(f"t has the shape {t.shape}")
    return wrong


def step_loss(c, params, clean, noised, masked, t, chosen=None):
    """The whole step's loss for ``clean`` [B, L] and its noise: for
    ``jax.grad`` at small sizes. ``params`` is ``{"embedding", "layers":
    [..], "final_norm", "head"}``; ``chosen`` per layer [B, 2L, k] or
    None."""
    half = clean.shape[1]
    x = params["embedding"][jnp.concatenate([noised, clean], axis=1)]
    for i, p in enumerate(params["layers"]):
        x = jnp.stack([
            layer(c, p, x[b], None if chosen is None else chosen[i][b])
            for b in range(x.shape[0])])
    scored = x[:, :half]
    return head_loss(c, params["head"], params["final_norm"],
                     scored.reshape(-1, x.shape[-1]), clean.reshape(-1),
                     loss_weights(c, masked, t).reshape(-1), clean.size)


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's keys (the
    published ``config.json``'s, Qwen3-MoE's names)."""
    objective = config["objective"]
    assert objective["kind"] == "block_diffusion", objective
    return {
        "hidden": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "top_k": int(config["num_experts_per_tok"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "held": int(config["num_experts"]),
        "first_held": int(config.get("first_expert_held", 0)),
        "layers": int(config["num_hidden_layers"]),
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "block_length": int(objective["block_length"]),
        "t_min": float(objective["t_min"]),
        "mask_id": int(config["vocab_size"]) - 1}

"""Plain reference for one training step of one chip's share of
SmallThinker-21BA3B (PowerInfer 2025,
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct): the
forward pass, the loss, its gradients by ``jax.grad``/``jax.vjp`` and
Adam, in float32 ``jax.numpy`` at ``default_matmul_precision("highest")``
(callers set it: ``with PRECISION:``), with no kernel, no sorting of
tokens by expert, no bfloat16 and nothing imported from the program
(``multiverso_tpu/models/lm``).

For a layer's input ``x`` [T, hidden] (``layer``):

    p = softmax(x W_r) over the 64 routed experts;  S = the six largest;
    w_e = p_e / sum_S p
    a = x + Attn(RMSNorm(x)) W_o: 28 query heads over 4 key-value heads
        (query head i reads key-value head i // 7), scale 1/sqrt(128),
        causal; a layer with ``rope`` turns q and k by rotary positions
        (theta 1.5e6, the halves paired) and one with a ``window`` sees
        only positions ``i - window < j <= i``
    y = a + sum_{e in S, e held} w_e W_d,e (relu(h W_g,e) * (h W_u,e)),
        h = RMSNorm(a)

then a final RMSNorm, logits over the head's rows, and the mean cross
entropy of the next token (``head_loss``).

Departures from the published model, each the configuration's
(benchmark/configs/smallthinker-21ba3b-l4.json) and the program's alike:
- **the share**: experts ``first .. first + held - 1`` of the 64 are
  here; experts outside add nothing, and ``w_e`` is still normalised
  over all six. Embedding and head have a slice of the vocabulary's
  rows, and the loss is over the slice;
- the router reads the layer's RAW input (the catalog says only "router
  placed before attention");
- the six may be GIVEN (``chosen``): on the chip the program's residuals
  differ from these at bfloat16 rounding from layer 1 on, and a near-tie
  would flip an expert, so the check hands the program's sets over and
  reports how many tokens' sets differ from this file's own choice;
- every expert is computed over every token and weighted by ``w_e`` or
  by 0: plain, and sixteen times the program's work.

Adam (``adam``): ``m = b1 m + (1-b1) g; v = b2 v + (1-b2) g g;
w -= lr (m / (1-b1^t)) / (sqrt(v / (1-b2^t)) + eps)``, t counted from 1
a table. For the embedding it is LAZY (``adam_rows``), as the server's
rows form is: the rows a step names (their gradients summed over equal
ids) and their moment rows are updated with the table's t, every other
row and its moments stay as they are.

Memory: ``attention`` goes a block of queries at a time and ``experts``
an expert at a time, each under ``jax.checkpoint``, so that a layer's
gradient at 8192 positions fits beside the tables; callers go a
sequence and a layer at a time.
"""

import jax
import jax.numpy as jnp

PRECISION = jax.default_matmul_precision("highest")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """[T, heads, d] turned by position, the halves paired."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, block=1024):
    """q [T, heads, d], k and v [T, kv heads, d] -> [T, heads, d]."""
    t, heads, d = q.shape
    per = heads // k.shape[1]
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
    block = min(block, t)
    assert t % block == 0

    @jax.checkpoint
    def one(args):
        qb, first = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(d))
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


def routing(c, router, x, chosen=None):
    """``(chosen [T, k], weights [T, experts])``: the weight of every
    expert for every token, zero outside the token's set of k."""
    p = jax.nn.softmax(x @ router, axis=-1)
    if chosen is None:
        chosen = jax.lax.top_k(p, c["top_k"])[1]
    inside = jnp.zeros(p.shape, bool).at[
        jnp.arange(p.shape[0])[:, None], chosen].set(True)
    kept = jnp.where(inside, p, 0.0)
    return chosen, kept / jnp.sum(kept, -1, keepdims=True)


def experts(c, h, weights, w_gate, w_up, w_down):
    """The held experts' part of the sum: ``weights`` [T, held]."""
    held, hidden, width = c["held"], c["hidden"], c["expert_width"]

    @jax.checkpoint
    def one(acc, e):
        gate, up, down, w = e
        return acc + w[:, None] * ((jax.nn.relu(h @ gate) * (h @ up))
                                   @ down), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        w_gate.reshape(held, hidden, width), w_up.reshape(held, hidden, width),
        w_down.reshape(held, width, hidden), weights.T))
    return acc


def layer(c, rope, window, p, x, chosen=None):
    """One sequence ``x`` [T, hidden] through one layer whose tensors
    ``p`` are named and shaped as the server's tables are."""
    t = x.shape[0]
    _, weights = routing(c, p["router"], x, chosen)
    h = rmsnorm(x, p["norm_attn"], c["eps"])
    q = (h @ p["wq"]).reshape(t, c["heads"], c["head_dim"])
    k = (h @ p["wk"]).reshape(t, c["kv_heads"], c["head_dim"])
    v = (h @ p["wv"]).reshape(t, c["kv_heads"], c["head_dim"])
    if rope:
        q, k = rotary(q, c["rope_theta"]), rotary(k, c["rope_theta"])
    a = x + attention(q, k, v, window).reshape(t, -1) @ p["wo"]
    h = rmsnorm(a, p["norm_ffn"], c["eps"])
    first = c["first_held"]
    return a + experts(c, h, weights[:, first:first + c["held"]],
                       p["w_gate"], p["w_up"], p["w_down"])


def head_loss(c, head, norm, x, targets, total):
    """Sum of the next-token cross entropy over ``x`` [N, hidden], over
    ``total`` (the step's token count: the mean's denominator)."""
    logits = rmsnorm(x, norm, c["eps"]) @ head.T
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - picked) / total


def kinds(c):
    """Per layer ``(rope, window)``."""
    return [(bool(r), c["window"] if w else 0)
            for r, w in zip(c["rope_layout"], c["window_layout"])]


def step_loss(c, params, tokens, chosen=None):
    """The whole step's loss for ``tokens`` [B, T+1]: for ``jax.grad`` at
    small sizes. ``params`` is ``{"embedding", "layers": [..],
    "final_norm", "head"}``; ``chosen`` per layer [B, T, k] or None."""
    ids, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embedding"][ids]
    for i, (rope, window) in enumerate(kinds(c)):
        x = jnp.stack([
            layer(c, rope, window, params["layers"][i], x[b],
                  None if chosen is None else chosen[i][b])
            for b in range(x.shape[0])])
    return head_loss(c, params["head"], params["final_norm"],
                     x.reshape(-1, x.shape[-1]), targets.reshape(-1),
                     targets.size)


def adam(w, m, v, t, g, lr, b1, b2, eps):
    """One Adam step, ``t`` already counted: ``(w, m, v)``."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    m_hat = m / (1 - b1 ** jnp.float32(t))
    v_hat = v / (1 - b2 ** jnp.float32(t))
    return w - lr * m_hat / (jnp.sqrt(v_hat) + eps), m, v


def adam_rows(w, m, v, t, ids, g_rows, lr, b1, b2, eps):
    """Lazy Adam: ``g_rows`` [.., hidden] are the gradients of the rows
    ``ids`` name; equal ids' gradients are summed, the rows named are
    stepped with the table's ``t``, the others stay."""
    ids = ids.reshape(-1)
    g = jnp.zeros_like(w).at[ids].add(g_rows.reshape(ids.size, -1))
    named = jnp.zeros(w.shape[0], bool).at[ids].set(True)[:, None]
    w1, m1, v1 = adam(w, m, v, t, g, lr, b1, b2, eps)
    return (jnp.where(named, w1, w), jnp.where(named, m1, m),
            jnp.where(named, v1, v))


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's keys."""
    n = int(config["num_hidden_layers"])
    return {
        "hidden": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config["head_dim"]),
        "top_k": int(config["moe_num_active_primary_experts"]),
        "expert_width": int(config["moe_ffn_hidden_size"]),
        "held": int(config["moe_num_primary_experts"]),
        "first_held": int(config.get("first_expert_held", 0)),
        "rope_layout": list(config["rope_layout"][:n]),
        "window_layout": list(config["sliding_window_layout"][:n]),
        "window": int(config["sliding_window_size"]),
        "rope_theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"])}

"""Plain reference for one next-token training step of one chip's share
of Keye-VL-2.0-30B-A3B's language model (Kwai-Keye 2026,
https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B, ``model_type:
KeyeVL2``: Qwen3-MoE's block with a DeepSeek-Sparse-Attention indexer in
every layer and rotary positions in three sections): the forward pass,
both losses, their gradients by ``jax.grad``/``jax.vjp`` and Adam, in
float32 ``jax.numpy`` at ``default_matmul_precision("highest")`` (callers
set it: ``with PRECISION:``), with no kernel, no bfloat16 and nothing
imported from the program (``multiverso_tpu/models/lm``). Adam, the norm
and the router's weights are lm_step.py's and the silu experts
lm_bd_step.py's, which the references share.

For a layer's input ``x`` [T, hidden] at positions ``pos`` [3, T]
(``layer``), ``sg`` = stop-gradient:

    h  = RMSNorm(x; g_attn)
    q  = RMSNorm_head(h Wq; g_q)   k = RMSNorm_head(h Wk; g_k)   v = h Wv
         (32 / 4 / 4 heads of 128), then the rotary turn: lane pair i of 64
         takes position row 0 | 1 | 2 for i in 0-15 | 16-39 | 40-63
         (``mrope_section`` laid out in runs), theta 1e7, halves paired
    qI_j = sg(h) W_qI  (16 heads of 64)    kI = LayerNorm(sg(h) W_kI)  (64)
         both turned by position row 0, every lane pair, theta 1e7
    w    = sg(h) W_w 16^-1/2 64^-1/2       (16)
    I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s]),  s <= t
    S_t  = the ``topk`` keys s <= t of largest I[t, s] (all of them while
           t < topk); equal scores: the earlier key first (``lax.top_k``)
    o_i[t] = sum_{s in S_t} softmax_{s in S_t}(q_i[t] . k[s] 128^-1/2) v[s]
    a  = x + concat(o) Wo
    u  = RMSNorm(a; g_ffn);  p = softmax(u W_r) over 128;  S = top-8 of p;
    y  = a + sum_{e in S, e held} (p_e / sum_S p) W_d,e (silu(u W_g,e) * (u W_u,e))
    L_I = sum_t KL( P_t || softmax_{s in S_t} I[t, s] ),
          P_t = sg( sum_i softmax_i[t, .] ) / 32 over S_t

The step's loss is the mean next-token cross entropy over the head's rows
plus the layers' ``L_I``. The cross entropy reaches no indexer tensor and
``L_I`` reaches the indexer's five alone: that falls out of the
stop-gradients above under ``jax.grad``, nothing is zeroed by hand.

Both choices may be GIVEN: each token's experts (``chosen``, as
lm_step.py) and each query's keys (``selected`` [T, T] bool). On the chip
the program's index scores differ from these at bfloat16 rounding, and a
near-tie at a query's 2,048th score would swap a key; the check hands the
program's sets over and reports the share of choices on which this file's
own departs from them (``layer(..., own=True)``). That share has a floor
(the inputs' rounding), under which a rounded score or an approximate
top-k would hide: so the check also hands over the program's index INPUTS
(``program``), and this file's top-k of the scores that the stated
arithmetic makes of them (``stated_scores``) has to be the program's
selection but for the order of the float32 sums.

Memory: ``attend`` goes 256 queries at a time under ``jax.checkpoint``
(index scores, selection, attention and the divergence of a block
together: [heads, 256, T] float32 at most), ``experts`` an expert at a
time; callers go a layer at a time.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.lm_bd_step import experts
from benchmark.reference.lm_step import (  # noqa: F401 - callers use them
    PRECISION, adam, adam_rows, head_loss, rmsnorm, routing)


def layernorm(x, scale, offset, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + offset


def rotary(x, pos, theta, sections=None):
    """[T, heads, d] turned, the halves paired. ``pos`` [3, T]: lane pair
    ``i`` takes the row of its section (``sections`` pairs each, in runs);
    without sections every pair takes row 0."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    row = jnp.zeros(d // 2, jnp.int32) if sections is None else jnp.repeat(
        jnp.arange(len(sections)), jnp.asarray(sections),
        total_repeat_length=d // 2)
    angle = pos.astype(jnp.float32)[row, :].T * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def indexer(c, p, h, pos):
    """``(qI [T, heads, dim], kI [T, dim], w [T, heads])`` from the
    DETACHED normed input."""
    h = jax.lax.stop_gradient(h)
    t, heads, dim = h.shape[0], c["index_heads"], c["index_dim"]
    qi = rotary((h @ p["wq_index"]).reshape(t, heads, dim), pos,
                c["rope_theta"])
    ki = layernorm(h @ p["wk_index"], p["index_norm_g"], p["index_norm_b"],
                   c["eps"])
    ki = rotary(ki[:, None, :], pos, c["rope_theta"])[:, 0]
    return qi, ki, (h @ p["w_index"]) * (heads * dim) ** -0.5


def own_selection(c, scores, causal):
    """This file's own choice for a block of queries: ``scores`` [R, T]
    -> bool [R, T]."""
    r, t = scores.shape
    top = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                        min(c["topk"], t))[1]
    return jnp.zeros((r, t), bool).at[jnp.arange(r)[:, None], top].set(
        True) & causal


def stated_scores(qi, ki, w):
    """The index scores of a block of queries as the configuration's
    ``guarantees`` state them, from GIVEN inputs: the heads' products take
    bfloat16 inputs, everything after is float32."""
    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    return jnp.sum(w[:, :, None] * jax.nn.relu(
        jnp.einsum("rjd,kd->rjk", rounded(qi), rounded(ki))), axis=1)


def attend(c, q, k, v, qi, ki, w, selected=None, own=False, rows=256,
           program=None):
    """q [T, heads, d], k and v [T, kv heads, d], the indexer's three ->
    ``(o [T, heads, d], L_I, (choices, choices of ``selected`` that this
    file's own selection lacks, choices that the EXACT selection of the
    program's own index inputs lacks))``; the last is zeros without
    ``own``. ``program`` is the program's ``(qI, kI, w)``: the third count
    is of ``selected`` against this file's top-k of ``stated_scores`` of
    them, which departs from an exact selection of float32 scores by the
    order of the sums alone (0 without ``program``)."""
    t, heads, d = q.shape
    per = heads // k.shape[1]
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
    rows = min(rows, t)
    assert t % rows == 0

    @jax.checkpoint
    def one(args):
        qb, qib, wb, given, theirs, first = args
        i = first + jnp.arange(rows)[:, None]
        causal = jnp.arange(t)[None, :] <= i
        scores = jnp.sum(wb[:, :, None] * jax.nn.relu(
            jnp.einsum("rjd,kd->rjk", qib, ki)), axis=1)
        mine = own_selection(c, scores, causal) \
            if own or selected is None else None
        sel = mine if selected is None else given
        s = jnp.einsum("rhd,khd->hrk", qb, k) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(sel, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hrk,khd->rhd", probs, v)
        target = jax.lax.stop_gradient(jnp.sum(probs, axis=0) / heads)
        log_pi = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), -1)
        live = sel & (target > 0)
        kl = jnp.sum(jnp.where(
            live, target * (jnp.log(jnp.where(live, target, 1.0))
                            - jnp.where(live, log_pi, 0.0)), 0.0))
        if not own:
            return o, kl, jnp.zeros(3, jnp.int32)
        inexact = 0 if program is None else jnp.sum(sel & ~own_selection(
            c, stated_scores(theirs[0], program[1], theirs[1]), causal))
        return o, kl, jnp.stack([jnp.sum(sel), jnp.sum(sel & ~mine),
                                 inexact]).astype(jnp.int32)

    n = t // rows
    given = jnp.zeros((n, rows, 1), bool) if selected is None \
        else selected.reshape(n, rows, t)
    theirs = () if program is None else (
        program[0].reshape((n, rows) + program[0].shape[1:]),
        program[2].reshape(n, rows, -1))
    o, kl, counts = jax.lax.map(one, (
        q.reshape(n, rows, heads, d), qi.reshape((n, rows) + qi.shape[1:]),
        w.reshape(n, rows, -1), given, theirs, jnp.arange(0, t, rows)))
    return o.reshape(t, heads, d), jnp.sum(kl), jnp.sum(counts, axis=0)


def layer(c, p, x, pos, chosen=None, selected=None, own=False,
          program=None):
    """One sequence ``x`` [T, hidden] at ``pos`` [3, T] through one layer
    whose tensors ``p`` are named and shaped as the server's tables:
    ``(y, L_I)``, and with ``own`` also ``(the experts this file would
    choose [T, k], ``attend``'s three counts)``; ``program`` as
    ``attend``'s."""
    t, eps = x.shape[0], c["eps"]
    h = rmsnorm(x, p["norm_attn"], eps)
    q = rmsnorm((h @ p["wq"]).reshape(t, c["heads"], c["head_dim"]),
                p["norm_q"], eps)
    k = rmsnorm((h @ p["wk"]).reshape(t, c["kv_heads"], c["head_dim"]),
                p["norm_k"], eps)
    v = (h @ p["wv"]).reshape(t, c["kv_heads"], c["head_dim"])
    q = rotary(q, pos, c["rope_theta"], c["sections"])
    k = rotary(k, pos, c["rope_theta"], c["sections"])
    o, index_loss, counts = attend(c, q, k, v, *indexer(c, p, h, pos),
                                   selected, own, program=program)
    a = x + o.reshape(t, -1) @ p["wo"]
    u = rmsnorm(a, p["norm_ffn"], eps)
    _, weights = routing(c, p["router"], u, chosen)
    first = c["first_held"]
    y = a + experts(c, u, weights[:, first:first + c["held"]],
                    p["w_gate"], p["w_up"], p["w_down"])
    if own:
        return y, index_loss, routing(c, p["router"], u)[0], counts
    return y, index_loss


def positions(t):
    """Text: the three rows equal."""
    return jnp.tile(jnp.arange(t)[None, :], (3, 1))


def step_losses(c, params, tokens, pos=None, chosen=None, selected=None):
    """``(cross entropy, sum of the layers' L_I)`` for ``tokens`` [B,
    T+1]: for ``jax.grad`` of their sum at small sizes. ``params`` is
    ``{"embedding", "layers": [..], "final_norm", "head"}``; ``chosen``
    per layer [B, T, k] and ``selected`` per layer [B, T, T], or None."""
    ids, targets = tokens[:, :-1], tokens[:, 1:]
    pos = positions(ids.shape[1]) if pos is None else pos
    x, index_loss = params["embedding"][ids], 0.0
    for i, p in enumerate(params["layers"]):
        out = [layer(c, p, x[b], pos,
                     None if chosen is None else chosen[i][b],
                     None if selected is None else selected[i][b])
               for b in range(x.shape[0])]
        x = jnp.stack([y for y, _ in out])
        index_loss = index_loss + sum(li for _, li in out)
    return head_loss(c, params["head"], params["final_norm"],
                     x.reshape(-1, x.shape[-1]), targets.reshape(-1),
                     targets.size), index_loss


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's keys (the
    published ``config.json``'s)."""
    sa = config["sa_config"]
    assert int(sa["indexer_num_kv_heads"]) == 1, sa
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
        "sections": tuple(int(s) for s in
                          config["rope_scaling"]["mrope_section"]),
        "eps": float(config["rms_norm_eps"]),
        "index_heads": int(sa["indexer_num_heads"]),
        "index_dim": int(sa["indexer_head_dim"]),
        "topk": int(sa["topk"])}

"""Plain reference for one training step of one chip's share of LFM2-8B-A1B
(LiquidAI 2025,
https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json,
``model_type: lfm2_moe``): the forward pass, the loss, its gradients by
``jax.grad``/``jax.vjp``, Adam and the router bias's step, in float32
``jax.numpy`` at ``default_matmul_precision("highest")`` (callers set it:
``with PRECISION:``), with no kernel, no sorting of tokens by expert, no
bfloat16 and nothing imported from the program
(``multiverso_tpu/models/lm``). Adam, the norm and the rotary turn are
lm_step.py's, the blocks of queries under a causal mask, the router through
its bias, the experts and the head's loss lm_mla_step.py's, which the
references share.

With ``h`` a layer's input [T, 2048], ``x = RMSNorm(h; g_op)``, ``a = h +
Mix(x)``, ``u = RMSNorm(a; g_ffn)``, ``y = a + FFN(u)``, eps 1e-5:

**A convolution layer's mixer** (``layer_types`` ``conv``; ``conv_f``; the
released ``Lfm2MoeShortConv``: ``in_proj``, ``B * x``, a ``Conv1d`` of kernel
``conv_L_cache`` with ``groups = hidden`` and left padding, ``C *
conv_out``, ``out_proj``):

    (B, C, X) = split_3(x W_in)         W_in [2048, 6144]
    z = B * X
    c[t] = w[:, 0] z[t - 2] + w[:, 1] z[t - 1] + w[:, 2] z[t]
                                        THREE SHIFTED SUMS written out
                                        (``taps``); z zero before the
                                        sequence; w [2048, 3], a channel
                                        reads no other; no bias, NO activation
    Mix(x) = (C * c) W_out              W_out [2048, 2048]

**An attention layer's** (``full_attention``; ``attention_f``): ``q = x W_q``
[T, 32, 64], ``k = x W_k``, ``v = x W_v`` [T, 8, 64]; q and k through an
RMSNorm a head (weights [64] each), then the rotary turn of all 64 lanes at
theta 1e6 (the halves paired); query head ``i`` reads key-value head ``i //
4``; causal softmax of ``q . k 64^-1/2`` as a masked matrix a block of
queries; ``Mix(x) = o W_o``. No gate, no window.

**Feed-forward** (``feed_forward``). Layers ``< num_dense_layers``: ``W_d
(silu(u W_g) * (u W_u))``, width 7168. The others: ``s = sigmoid(u W_r)``
[32]; the 4 largest of ``s + bias``; ``w_e = routed_scaling_factor s_e /
sum_S s``; ``FFN(u) = sum_{e in S, e held} w_e E_e(u)``: NO shared expert.
The bias gets no gradient: after a step ``bias_e += gamma sign(mean(load) -
load_e)``, gamma ASSUMED 0.001.

**One table** (``tie_word_embeddings``): ``step_loss`` takes ``params``
WITHOUT a ``head``; the embedding table is used twice, for the rows and as
``E^T`` after the final norm, so ``jax.grad`` gives it the sum of both uses'
gradients by construction.

Departures from the published model, each the configuration's
(benchmark/configs/lfm2-8b-a1b-l8.json) and the program's alike:
- **the share**: experts ``first .. first + held - 1`` of the 32 (``w_e``
  over all four), a slice of the vocabulary's rows; what the absent experts
  would add is left out; both kinds of mixer, the dense MLPs, routers and
  norms are whole;
- the four may be GIVEN (``chosen``), as in lm_step.py;
- every held expert is computed over every token and weighted by ``w_e``
  or by 0.
"""

import jax
import jax.numpy as jnp

from benchmark.reference.lm_mla_step import (  # noqa: F401 - callers use them
    PRECISION, adam, adam_rows, attention, bias_step, experts, gated,
    head_loss, load_of, rmsnorm, routing)
from benchmark.reference.lm_step import rotary

KINDS = {"conv": "conv", "full_attention": "gqa"}


def taps(z, w):
    """z [T, channels], w [channels, 3]: position ``t`` reads ``t - 2``,
    ``t - 1`` and ``t``, zero before the sequence; written out."""
    nothing = jnp.zeros_like(z[:1])
    back_1 = jnp.concatenate([nothing, z[:-1]])
    back_2 = jnp.concatenate([nothing, nothing, z[:-2]])
    assert w.shape[1] == 3, w.shape
    return w[:, 0] * back_2 + w[:, 1] * back_1 + w[:, 2] * z


def conv_f(c, p, h):
    x = rmsnorm(h, p["norm_attn"], c["eps"])
    b, gate, into = jnp.split(x @ p["w_in"], 3, axis=-1)
    return (gate * taps(b * into, p["conv_w"])) @ p["w_out"]


def attention_f(c, p, h):
    t, heads, kv, d = h.shape[0], c["heads"], c["kv_heads"], c["head_dim"]
    x = rmsnorm(h, p["norm_attn"], c["eps"])
    q = rmsnorm((x @ p["wq"]).reshape(t, heads, d), p["norm_q"], c["eps"])
    k = rmsnorm((x @ p["wk"]).reshape(t, kv, d), p["norm_k"], c["eps"])
    v = (x @ p["wv"]).reshape(t, kv, d)
    q, k = rotary(q, c["rope_theta"]), rotary(k, c["rope_theta"])
    # query head i reads key-value head i // (heads / kv)
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    o = attention(q, k, v, d ** -0.5, block=min(1024, t))
    return o.reshape(t, -1) @ p["wo"]


def feed_forward(c, p, a, chosen=None):
    u = rmsnorm(a, p["norm_ffn"], c["eps"])
    if "router" not in p:
        return gated(u, p["w_gate"], p["w_up"], p["w_down"])
    _, weights = routing(c, p["router"], p["router_bias"], u, chosen)
    first = c["first_held"]
    return experts(c, u, weights[:, first:first + c["held"]], p["w_gate"],
                   p["w_up"], p["w_down"])


def layer(c, kind, p, x, chosen=None, own=False):
    """One sequence ``x`` [T, hidden] through one layer of ``kind`` (``conv``
    | ``gqa``: the CONFIGURATION's ``layer_types`` say, ``kinds``) whose
    tensors ``p`` are named and shaped as the server's tables; one with a
    ``router`` is sparse, one without dense. With ``own`` also the experts
    this file would choose itself ([T, k]; None in a dense layer), whatever
    ``chosen`` says."""
    a = x + (conv_f if kind == "conv" else attention_f)(c, p, x)
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
    "final_norm"}``: the ONE table is the head too; ``chosen`` per layer
    [B, T, k] or None."""
    ids, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embedding"][ids]
    for i, (kind, p) in enumerate(zip(c["kinds"], params["layers"])):
        given = None if chosen is None else chosen[i]
        if given is None:
            x = jax.vmap(lambda seq, p=p, k=kind: layer(c, k, p, seq))(x)
        else:
            x = jax.vmap(lambda seq, ids, p=p, k=kind: layer(
                c, k, p, seq, ids))(x, given)
    return head_loss(c, params["embedding"], params["final_norm"],
                     x.reshape(-1, x.shape[-1]), targets.reshape(-1),
                     targets.size)


def tied_gradient(d_head, ids, d_rows):
    """The one table's gradient from its two uses' (what ``jax.grad`` of
    ``step_loss`` gives whole): the head's [vocab, hidden] and a row a
    position."""
    return d_head.at[ids.reshape(-1)].add(
        d_rows.reshape(-1, d_rows.shape[-1]))


def kinds(config: dict):
    """Each held layer's kind of mixer, from ``layer_types`` as published:
    ``"conv"`` | ``"gqa"``."""
    n = int(config["num_hidden_layers"])
    return [KINDS[t] for t in config["layer_types"][:n]]


def sizes(config: dict) -> dict:
    """The reference's sizes from a configuration file's keys (the
    published ``config.json``'s; the experts are the HELD ones)."""
    hidden, heads = int(config["hidden_size"]), int(
        config["num_attention_heads"])
    return {
        "hidden": hidden, "heads": heads,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or hidden // heads),
        "rope_theta": float(config["rope_theta"]),
        "kinds": kinds(config),
        "outputs": int(config["router_outputs"]),
        "top_k": int(config["num_experts_per_tok"]),
        "expert_width": int(config["moe_intermediate_size"]),
        "held": int(config["num_experts"]),
        "first_held": int(config.get("first_expert_held", 0)),
        "routed_scale": float(config["routed_scaling_factor"]),
        "bias_rate": float(config["router_bias_rate"]),
        "layers": int(config["num_hidden_layers"]),
        "dense_layers": int(config["num_dense_layers"]),
        "eps": float(config["norm_eps"])}

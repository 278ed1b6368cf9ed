"""Operations and bytes a training step of the sixth family of language
model has to do (benchmark/configs/kimi-linear-48b-a3b-l5.json: layers
whose attention is the gated delta rule's scan behind short convolutions,
layers of latent attention without a query latent, a shared expert), from
its sizes and from what the step's counters saw
(benchmark/drivers/lm_kda.py fills ``ctx.shapes``; the counters are the
trainer's). The counting rules are lmshapes.py's: what the MODEL needs,
once, whatever implements it; the backward pass at twice the forward, the
layer's recomputation not at all.

The work is counted BY LAYER KIND (``attention_layout``: ``kda`` | ``mla``
a layer), so that no layer is read as another's. The scan is counted as the
recurrence the model states, a position a head: the decay of the state (K V
multiplies), the state's product with k, the rank-one write and the
state's product with q (2 K V operations each), and the bytes of q, k, g
[K], v [V] and beta in and o [V] out in float32. A chunked form does more
arithmetic (the chunk's own pairs, the solve) and moves more (the chunk's
matrices, the state at boundaries): counted as the model's, its share can
only read low, and no share can pass 100% whatever the chunk.
"""

from benchmark.lib import lmshapes

PASSES = lmshapes.PASSES
#: This family's row of lib/families.py. No ATTENTION_SCOPES: the one latent
#: layer's kernel has had no roofline of its own in this family's cell.
COUNTERS = lmshapes.COUNTERS


def layers_of(s: dict, kind: str) -> int:
    return sum(k == kind for k in s["attention_layout"])


def tokens(s: dict) -> int:
    return s["sequences"] * s["seq_len"]


def scan_flops(s: dict) -> int:
    """The recurrence of ONE delta layer over a step's tokens, forward and
    backward: 7 K V operations a position a head forward."""
    return (PASSES * 7 * s["kda_head_dim"] ** 2 * s["kda_heads"]
            * tokens(s))


def scan_bytes(s: dict) -> int:
    """The least ONE delta layer's scan moves: q, k, g, v, beta read and o
    written, float32, a pass."""
    d = s["kda_head_dim"]
    return PASSES * 4 * (5 * d + 1) * s["kda_heads"] * tokens(s)


def conv_flops(s: dict) -> int:
    """One delta layer's three convolutions: a multiply and an add a
    weight a channel a position."""
    lanes = s["kda_heads"] * s["kda_head_dim"]
    return PASSES * 3 * 2 * s["kda_conv"] * lanes * tokens(s)


def kda_dense_flops(s: dict) -> int:
    """One delta layer's projections a token, forward: ``W_q``, ``W_k``,
    ``W_v``, ``W_o``, the two low-rank pairs and beta's."""
    h, d = s["hidden"], s["kda_head_dim"]
    lanes = s["kda_heads"] * d
    return 2 * (4 * h * lanes + 2 * h * d + 2 * d * lanes
                + h * s["kda_heads"])


def mla_dense_flops(s: dict) -> int:
    """One latent layer's projections a token, forward: ``W_q``, ``W_kva``,
    ``W_kvb``, ``W_o``."""
    h, heads = s["hidden"], s["mla_heads"]
    nope = s["qk_dim"] - s["rope_dim"]
    return 2 * (h * heads * s["qk_dim"] + h * (s["kv_rank"] + s["rope_dim"])
                + s["kv_rank"] * heads * (nope + s["v_dim"])
                + heads * s["v_dim"] * h)


def mla_attention_flops(s: dict) -> int:
    """The attention proper of ONE latent layer: causal pairs, ``2 (qk +
    v)`` operations a pair a head, forward and backward."""
    pairs = lmshapes.attention_pairs(s["seq_len"], 0)
    return (PASSES * 2 * (s["qk_dim"] + s["v_dim"]) * s["mla_heads"]
            * s["sequences"] * pairs)


def token_flops(s: dict) -> int:
    """The products every token goes through in a step, forward: each
    layer's projections by its kind, a dense layer's MLP, a sparse layer's
    router and shared expert, the head."""
    h = s["hidden"]
    sparse = 2 * h * s["router_outputs"] + 3 * 2 * h * s["shared_width"]
    return (layers_of(s, "kda") * kda_dense_flops(s)
            + layers_of(s, "mla") * mla_dense_flops(s)
            + s["dense_layers"] * 3 * 2 * h * s["dense_width"]
            + s["sparse_layers"] * sparse + 2 * h * s["vocab"])


def step_flops(steps: int, assignments: int, s: dict) -> int:
    """Operations of ``steps`` steps whose sparse layers saw
    ``assignments`` assignments on held experts in all."""
    delta = layers_of(s, "kda") * (scan_flops(s) + conv_flops(s))
    latent = layers_of(s, "mla") * mla_attention_flops(s)
    return (steps * (delta + latent + PASSES * tokens(s) * token_flops(s))
            + lmshapes.expert_flops(assignments, s["hidden"],
                                    s["expert_width"]))

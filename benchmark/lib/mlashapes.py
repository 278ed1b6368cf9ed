"""Operations and bytes a training step of the third family of language
model has to do (latent attention, residual streams, a shared expert, a
multi-token module), from its sizes and from what the step's counters saw
(benchmark/drivers/lm_mla.py fills ``ctx.shapes``; the counters are the
trainer's). The counting rules are lmshapes.py's: what the mathematics
needs, once; the backward pass at twice the forward, the layer's
recomputation not at all.

``sparse_layers`` and ``dense_layers`` count the main model's layers,
``modules`` the multi-token modules held (0 or 1): a module is one more
sparse layer, one projection ``[2 hidden, hidden]`` and one more pass of
the head. Every layer has the latent attention over ``heads_held`` heads
and two stream sublayers.
"""

from benchmark.lib import lmshapes

PASSES = lmshapes.PASSES
COUNTERS = lmshapes.COUNTERS            # this family's row of lib/families.py
ATTENTION_SCOPES = ("mv.lm.attn.mla.kernel",)


def blocks(s: dict) -> int:
    """Layers a step runs, the modules' among them."""
    return s["sparse_layers"] + s["dense_layers"] + s["modules"]


def attention_flops(s: dict) -> int:
    """The attention proper of ONE layer: causal pairs, ``2 (qk + v)``
    operations a pair a held head (scores over the published 192 lanes,
    the product with v over 128), forward and backward."""
    pairs = lmshapes.attention_pairs(s["seq_len"], 0)
    return (PASSES * 2 * (s["qk_dim"] + s["v_dim"]) * s["heads_held"]
            * s["sequences"] * pairs)


def attention_step_flops(s: dict) -> int:
    """ONE step's attention proper: every layer's, the modules' among
    them."""
    return blocks(s) * attention_flops(s)


def attention_dense_flops(s: dict) -> int:
    """The latent projections of one layer a token, forward: ``W_qa``,
    ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o`` over the held heads."""
    h, heads = s["hidden"], s["heads_held"]
    nope = s["qk_dim"] - s["rope_dim"]
    return 2 * (h * s["q_rank"] + s["q_rank"] * heads * s["qk_dim"]
                + h * (s["kv_rank"] + s["rope_dim"])
                + s["kv_rank"] * heads * (nope + s["v_dim"])
                + heads * s["v_dim"] * h)


def mixer_flops(s: dict) -> int:
    """One sublayer's coefficient product a token, forward: ``r phi^T``,
    ``[n hidden] x [2n + n^2]`` (the mixes themselves are a few operations
    an element: bytes, not operations, bound them)."""
    n = s["streams"]
    return 2 * n * s["hidden"] * (2 * n + n * n)


def token_flops(s: dict) -> int:
    """The products every token goes through in a step, forward: every
    layer's latent projections and two mixers, a dense layer's MLP, a
    sparse layer's router and shared expert, a module's projection, the
    head once and once more a module."""
    h = s["hidden"]
    every = attention_dense_flops(s) + 2 * mixer_flops(s)
    sparse = 2 * h * s["router_outputs"] + 3 * 2 * h * s["shared_width"]
    return (blocks(s) * every
            + s["dense_layers"] * 3 * 2 * h * s["dense_width"]
            + (s["sparse_layers"] + s["modules"]) * sparse
            + s["modules"] * 2 * 2 * h * h
            + (1 + s["modules"]) * 2 * h * s["vocab"])


def step_flops(steps: int, assignments: int, s: dict) -> int:
    """Operations of ``steps`` steps whose sparse layers saw
    ``assignments`` assignments on held experts in all."""
    tokens = s["sequences"] * s["seq_len"]
    return (steps * (attention_step_flops(s)
                     + PASSES * tokens * token_flops(s))
            + lmshapes.expert_flops(assignments, s["hidden"],
                                    s["expert_width"]))


def stream_bytes(steps: int, s: dict) -> int:
    """The least the stream mixers move: each sublayer reads the stream
    tensor ``[tokens, n hidden]`` once and writes it once a pass, in
    float32 as it is stored; two sublayers a layer."""
    tokens = s["sequences"] * s["seq_len"]
    tensor = tokens * s["streams"] * s["hidden"] * 4
    return steps * blocks(s) * 2 * PASSES * 2 * tensor

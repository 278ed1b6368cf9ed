"""Operations a training step of the fourth family of language model has
to do (grouped-query attention of two kinds, each with its own query heads
and mask, a per-head output gate, a dense layer and sparse ones with a
shared expert), from its sizes and from what the step's counters saw
(benchmark/drivers/lm_mixed.py fills ``ctx.shapes``; the counters are the
trainer's). Counted once, a layer KIND at a time: a layer's kind is its
``(query heads, windowed, sparse)``, and each kind has its own heads and
its own unmasked pairs. The counting rules are lmshapes.py's: what the
mathematics needs, once; the backward pass at twice the forward, the
layer's recomputation not at all.

``heads_layout``, ``windowed`` and ``ffn_layout`` are per layer;
``layers`` counts the SPARSE layers (what the shared readers of the
experts count by).
"""

from benchmark.lib import lmshapes

PASSES = lmshapes.PASSES
COUNTERS = lmshapes.COUNTERS            # this family's row of lib/families.py
ATTENTION_SCOPES = lmshapes.ATTENTION_SCOPES    # both kinds' kernels


def pairs(s: dict, windowed) -> int:
    """Unmasked (query, key) pairs of one head over one sequence: causal
    ``T (T + 1) / 2``, under the window ``sum_p min(p + 1, window)``."""
    return lmshapes.attention_pairs(s["seq_len"],
                                    s["window"] if windowed else 0)


def attention_flops(s: dict) -> int:
    """The attention proper of every layer: its kind's unmasked pairs, ``2
    (128 + 128)`` operations a pair (scores and the product with v, each
    over a head's lanes) a query head of that kind, forward and
    backward."""
    return sum(PASSES * 2 * 2 * s["head_dim"] * heads * s["sequences"]
               * pairs(s, windowed)
               for heads, windowed in zip(s["heads_layout"],
                                          s["windowed"]))


attention_step_flops = attention_flops    # it counts every layer already


def layer_token_flops(s: dict, heads: int, sparse) -> int:
    """The products every token goes through in one layer, forward: q and
    o over the layer's own heads, k and v over the key-value heads, the
    gate's ``[hidden, heads]``; a dense layer's MLP, or a sparse layer's
    router and shared expert."""
    h, d = s["hidden"], s["head_dim"]
    attention = 2 * h * (2 * heads * d + 2 * s["kv_heads"] * d + heads)
    if not sparse:
        return attention + 3 * 2 * h * s["dense_width"]
    return attention + 2 * h * s["router_outputs"] \
        + 3 * 2 * h * s["shared_width"]


def token_flops(s: dict) -> int:
    """Every layer's by its kind, and the head."""
    return sum(layer_token_flops(s, heads, sparse)
               for heads, sparse in zip(s["heads_layout"],
                                        s["ffn_layout"])) \
        + 2 * s["hidden"] * s["vocab"]


def step_flops(steps: int, assignments: int, s: dict) -> int:
    """Operations of ``steps`` steps whose sparse layers saw
    ``assignments`` assignments on held experts in all."""
    tokens = s["sequences"] * s["seq_len"]
    return (steps * (attention_flops(s) + PASSES * tokens * token_flops(s))
            + lmshapes.expert_flops(assignments, s["hidden"],
                                    s["expert_width"]))

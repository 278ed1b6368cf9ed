"""Operations a training step of the eighth family of language model has to
do (benchmark/configs/solar-open2-250b-a15b-l4.json: layers of grouped-query
softmax attention under a gate a lane and layers of the gated delta rule's
scan, both over a SHARE of their heads, every layer sparse with a shared
expert), from its sizes and from what the step's counters saw
(benchmark/drivers/lm_solar.py fills ``ctx.shapes``: ``heads``, ``kv_heads``
and ``kda_heads`` are the HELD ones, so nothing of the absent heads is
counted). The counting rules are lmshapes.py's: what the MODEL needs, once,
whatever implements it; the backward pass at twice the forward, the layer's
recomputation not at all.

The work is counted BY LAYER KIND (``attention_layout``: ``gqa`` | ``kda`` a
layer): a delta layer's projections, convolutions and recurrence are
kdashapes.py's over the held heads; a softmax layer's attention proper is
its unmasked (query, key) pairs x ``2 (128 + 128)`` operations a pair a held
query head; the experts' products follow the assignments the counter saw.
"""

from benchmark.lib import kdashapes, lmshapes

PASSES = lmshapes.PASSES
layers_of, tokens = kdashapes.layers_of, kdashapes.tokens
COUNTERS = lmshapes.COUNTERS            # this family's row of lib/families.py
ATTENTION_SCOPES = ("mv.lm.attn.full.kernel",)


def attention_flops(s: dict) -> int:
    """The attention proper of ONE softmax layer over its held query heads:
    causal pairs, forward and backward."""
    return lmshapes.attention_flops(s["sequences"], s["seq_len"], s["heads"],
                                    s["head_dim"], 0)


def attention_step_flops(s: dict) -> int:
    """ONE step's attention proper: every softmax layer's."""
    return layers_of(s, "gqa") * attention_flops(s)


def gqa_dense_flops(s: dict) -> int:
    """One softmax layer's projections a token, forward: ``W_q``, the lane
    gate's ``W_g`` and ``W_o`` over the held query heads, ``W_k`` and ``W_v``
    over the key-value heads they read."""
    h, d = s["hidden"], s["head_dim"]
    return 2 * h * d * (3 * s["heads"] + 2 * s["kv_heads"])


def token_flops(s: dict) -> int:
    """The products every token goes through in a step, forward: each
    layer's projections by its kind, every layer's router and shared expert,
    the head."""
    h = s["hidden"]
    sparse = 2 * h * s["router_outputs"] + 3 * 2 * h * s["shared_width"]
    return (layers_of(s, "kda") * kdashapes.kda_dense_flops(s)
            + layers_of(s, "gqa") * gqa_dense_flops(s)
            + s["sparse_layers"] * sparse + 2 * h * s["vocab"])


def step_flops(steps: int, assignments: int, s: dict) -> int:
    """Operations of ``steps`` steps whose layers saw ``assignments``
    assignments on held experts in all."""
    delta = layers_of(s, "kda") * (kdashapes.scan_flops(s)
                                   + kdashapes.conv_flops(s))
    softmax = attention_step_flops(s)
    return (steps * (delta + softmax + PASSES * tokens(s) * token_flops(s))
            + lmshapes.expert_flops(assignments, s["hidden"],
                                    s["expert_width"]))

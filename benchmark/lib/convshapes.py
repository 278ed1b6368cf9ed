"""Operations and bytes a training step of the ninth family of language
model has to do (benchmark/configs/lfm2-8b-a1b-l8.json: layers whose mixer
is a doubly gated short convolution, layers of grouped-query attention with
heads of 64 lanes, two dense layers and sparse ones with NO shared expert,
ONE table for embedding and head), from its sizes and from what the step's
counters saw (benchmark/drivers/lm_lfm2.py fills ``ctx.shapes``). The
counting rules are lmshapes.py's: what the MODEL needs, once, whatever
implements it; the backward pass at twice the forward, the layer's
recomputation not at all.

The work is counted BY LAYER KIND (``attention_layout``: ``conv`` | ``gqa`` a
layer): an attention layer's proper work is solarshapes.py's (its unmasked
(query, key) pairs x ``2 (64 + 64)`` operations a pair a query head), the
experts' products follow the assignments the counter saw and the head is
counted once (lmshapes.py's rules; the one table is one product). This file
adds the convolution layer: its two products and the elementwise chain
between them (two gates and ``conv_taps`` taps a channel a position), as
OPERATIONS: the compiler fuses most of the chain into the products' own
fusions, so no time on the device is the chain's alone and its bytes have
no time to be set against (PERF.md section 6, PR 63); the products bound
the mixer by compute.
"""

from benchmark.lib import kdashapes, lmshapes, solarshapes

PASSES = lmshapes.PASSES
layers_of, tokens = kdashapes.layers_of, kdashapes.tokens
attention_flops = solarshapes.attention_flops   # ONE attention layer's proper
attention_step_flops = solarshapes.attention_step_flops     # every gqa layer's
COUNTERS = lmshapes.COUNTERS            # this family's row of lib/families.py
ATTENTION_SCOPES = solarshapes.ATTENTION_SCOPES


def conv_dense_flops(s: dict) -> int:
    """One convolution layer's two products a token, forward: ``W_in``
    [hidden, 3 hidden] and ``W_out`` [hidden, hidden]."""
    return 2 * 4 * s["hidden"] ** 2


def chain_flops(s: dict) -> int:
    """One convolution layer's gates and taps over a step's tokens, forward
    and backward: two multiplies a channel for the gates, a multiply and an
    add a tap."""
    return PASSES * (2 + 2 * s["conv_taps"]) * s["hidden"] * tokens(s)


def mixer_flops(s: dict) -> int:
    """ONE convolution layer's mixer over a step's tokens, forward and
    backward: its two products and its chain."""
    return PASSES * tokens(s) * conv_dense_flops(s) + chain_flops(s)


def gqa_dense_flops(s: dict) -> int:
    """One attention layer's four projections a token, forward."""
    h, d = s["hidden"], s["head_dim"]
    return 2 * h * d * (2 * s["heads"] + 2 * s["kv_heads"])


def token_flops(s: dict) -> int:
    """The products every token goes through in a step, forward: each
    layer's projections by its kind, a dense layer's MLP, a sparse layer's
    router, the head (ONE table: one product)."""
    h = s["hidden"]
    return (layers_of(s, "conv") * conv_dense_flops(s)
            + layers_of(s, "gqa") * gqa_dense_flops(s)
            + s["dense_layers"] * 3 * 2 * h * s["dense_width"]
            + s["sparse_layers"] * 2 * h * s["router_outputs"]
            + 2 * h * s["vocab"])


def step_flops(steps: int, assignments: int, s: dict) -> int:
    """Operations of ``steps`` steps whose sparse layers saw ``assignments``
    assignments on held experts in all."""
    mixers = (layers_of(s, "conv") * chain_flops(s)
              + attention_step_flops(s))
    return (steps * (mixers + PASSES * tokens(s) * token_flops(s))
            + lmshapes.expert_flops(assignments, s["hidden"],
                                    s["expert_width"]))

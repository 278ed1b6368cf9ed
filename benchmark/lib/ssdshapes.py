"""Operations and bytes a training step of the tenth family of language
model has to do (benchmark/configs/granite-4.0-h-micro-l10.json: layers
whose mixer is a selective state-space layer, Mamba-2's, one layer of
grouped-query attention with heads of 64 lanes, a dense MLP in every layer,
NO router, ONE table for embedding and head), from its sizes
(benchmark/drivers/lm_granite.py fills ``ctx.shapes``). The counting rules
are lmshapes.py's: what the MODEL needs, once, whatever implements it; the
backward pass at twice the forward, the layer's recomputation not at all.

The work is counted BY LAYER KIND (``attention_layout``: ``ssd`` | ``gqa`` a
layer): an attention layer's projections and its proper work over the causal
pairs at 64 | 64 lanes are convshapes.py's, the dense MLP's and the head's
products lmshapes.py's rules. This file adds the state-space layer: its two
products, and its scan AS THE FUNCTION DEFINES IT at the chunk ``c`` that ran
(``ssd_chunk``), a position forward:

    C B^T over the pairs s <= t of its chunk      (c + 1) / 2 x 2 N
    ((C B^T) * L) (dt X), every head               (c + 1) / 2 x 2 H P
    the entering state read, S C_t                 2 H P N
    the position's part of the leaving state       2 H P N

(the pairs ABOVE the diagonal, which a product of whole ``c x c`` tiles also
computes, are masked work and are not counted; ``L``'s exponentials, the
cumulative sums, the convolution, the gate and the norm are no products and
are not counted as operations). The scan and the gate are bound by their
BYTES, which ``scan_bytes`` and ``gate_bytes`` count as the layer's own
arrays once a pass, whatever the chunk and whatever implements them.
"""

from benchmark.lib import convshapes, kdashapes, lmshapes

PASSES = lmshapes.PASSES
layers_of, tokens = kdashapes.layers_of, kdashapes.tokens
attention_flops = convshapes.attention_flops    # ONE attention layer's proper
attention_step_flops = convshapes.attention_step_flops      # every gqa layer's
gqa_dense_flops = convshapes.gqa_dense_flops
COUNTERS = ("LM_STEP",)     # this family's row of lib/families.py: no experts
ATTENTION_SCOPES = convshapes.ATTENTION_SCOPES

def inner(s: dict) -> int:
    return s["ssd_heads"] * s["ssd_head_dim"]


def ssd_dense_flops(s: dict) -> int:
    """One state-space layer's two products a token, forward: ``W_in``
    [hidden, 2 H P + 2 N + H] and ``W_out`` [H P, hidden]."""
    return 2 * s["hidden"] * (3 * inner(s) + 2 * s["ssd_state"]
                              + s["ssd_heads"])


def scan_flops(s: dict) -> int:
    """ONE state-space layer's scan over a step's tokens, forward and
    backward, at the chunk that ran: the module's docstring."""
    n, pairs = s["ssd_state"], (s["ssd_chunk"] + 1) / 2
    position = pairs * 2 * (n + inner(s)) + 4 * inner(s) * n
    return int(PASSES * position * tokens(s))


def scan_bytes(s: dict) -> int:
    """The least ONE state-space layer's scan moves over a step's tokens,
    forward and backward: X in and Y out (``2 H P``), B and C (``2 N``) and
    dt (``H``) a position a pass, float32 as the layer holds them. ``L``,
    the cumulative sums and the states between chunks are the
    implementation's and are not counted."""
    return (PASSES * 4 * (2 * inner(s) + 2 * s["ssd_state"] + s["ssd_heads"])
            * tokens(s))


def gate_bytes(s: dict) -> int:
    """The least ONE state-space layer's gate and norm move over a step's
    tokens: Y and z read and the normed product written forward; Y, z and
    the product's gradient read and two gradients written backward: eight
    float32 arrays of ``H P`` a position."""
    return 4 * 8 * inner(s) * tokens(s)


def token_flops(s: dict) -> int:
    """The products every token goes through in a step, forward: each
    layer's projections by its kind, every layer's dense MLP, the head (ONE
    table: one product)."""
    h = s["hidden"]
    return (layers_of(s, "ssd") * ssd_dense_flops(s)
            + layers_of(s, "gqa") * gqa_dense_flops(s)
            + len(s["attention_layout"]) * 3 * 2 * h * s["dense_width"]
            + 2 * h * s["vocab"])


def step_flops(steps: int, s: dict) -> int:
    """Operations of ``steps`` steps."""
    mixers = (layers_of(s, "ssd") * scan_flops(s)
              + attention_step_flops(s))
    return steps * (mixers + PASSES * tokens(s) * token_flops(s))

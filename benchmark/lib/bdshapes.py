"""Operations a block-diffusion training step has to do, from its sizes
and from what the step's counters saw (benchmark/drivers/lm_bd.py fills
``ctx.shapes``; the counters are the trainer's). The counting rules are
lmshapes.py's: what the mathematics needs, once; the backward pass at
twice the forward, the layer's recomputation not at all.

A step runs ``sequences`` sequences of ``seq_len`` clean tokens as
``2 * seq_len`` positions each (the noised copy and the clean one):
every layer's dense products and experts see all of them, the attention
the unmasked (query, key) pairs of the block mask, the head the noised
half alone. Every layer is counted alike: the last layer's clean half
feeds no loss (its keys and values do, its queries, experts and output
do not), the model's forward pass has it, the program computes it, and
it is counted; PERF.md section 7 names it as work a later PR can skip.
"""

from benchmark.lib import lmshapes

COUNTERS = lmshapes.COUNTERS            # this family's row of lib/families.py
ATTENTION_SCOPES = ("mv.lm.attn.blockdiff.kernel",)


def attention_pairs(seq_len: int, block: int) -> int:
    """Unmasked (query, key) pairs of one head over one sequence's two
    copies: a noised block sees itself (``L b`` pairs) and the clean
    blocks before it, the clean copy is causal by blocks (together
    ``L^2``); ``L^2 + L b`` of the ``4 L^2``."""
    return seq_len * seq_len + seq_len * block


def attention_flops(sequences: int, seq_len: int, heads: int, head_dim: int,
                    block: int) -> int:
    """Scores and the product with v over the unmasked pairs (2 x 2
    operations a pair and a lane), forward and backward, one layer."""
    return (lmshapes.PASSES * 4 * head_dim * heads * sequences
            * attention_pairs(seq_len, block))


def dense_flops(s: dict) -> int:
    """The products every position goes through in a step: the four
    attention projections and the router in each layer over both copies,
    and the head over the noised copy."""
    q = s["heads"] * s["head_dim"]
    kv = s["kv_heads"] * s["head_dim"]
    layer = 2 * s["hidden"] * (2 * q + 2 * kv + s["router_outputs"])
    tokens = s["sequences"] * s["seq_len"]
    return lmshapes.PASSES * tokens * (
        2 * s["layers"] * layer + 2 * s["hidden"] * s["vocab"])


def attention_step_flops(s: dict) -> int:
    """ONE step's attention proper, every layer."""
    return s["layers"] * attention_flops(
        s["sequences"], s["seq_len"], s["heads"], s["head_dim"],
        s["block_length"])


def step_flops(steps: int, assignments: int, s: dict) -> int:
    """Operations of ``steps`` steps whose layers saw ``assignments``
    assignments on held experts in all."""
    return (steps * (attention_step_flops(s) + dense_flops(s))
            + lmshapes.expert_flops(assignments, s["hidden"],
                                    s["expert_width"]))

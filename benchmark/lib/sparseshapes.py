"""Operations a training step of the model with a learned selection has
to do (benchmark/configs/keye-vl2-30b-a3b-lm.json), from its sizes and
from what the step's counters saw (benchmark/drivers/lm_sparse.py fills
``ctx.shapes``; the counters are the trainer's). The counting rules are
lmshapes.py's: what the MODEL needs, once, whatever implements it; the
backward pass at twice the forward, the layer's recomputation not at all.

So the attention is counted over the SELECTED (query, key) pairs alone
(``sum_t min(t + 1, topk)`` a head: a kernel that computes every causal
tile under the selection does four times that at 16,384 positions and
reads low), the index scores over every causal pair forward (the model
scores them all to select) and over the selected pairs backward (the
divergence reaches no other), the divergence's target once over the
selected pairs (the heads' scores; the product with v is the
attention's). No share computed from these can pass 100% by counting
masked, padded or repeated work.
"""

from benchmark.lib import lmshapes

COUNTERS = lmshapes.COUNTERS            # this family's row of lib/families.py
ATTENTION_SCOPES = ("mv.lm.attn.sparse.kernel",)


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def selected_pairs(seq_len: int, topk: int) -> int:
    """(query, key) pairs a head's attention reads over one sequence:
    every key before a query while there are fewer than ``topk``, then
    ``topk``."""
    full = min(seq_len, topk)
    return full * (full + 1) // 2 + (seq_len - full) * topk


def attention_flops(s: dict) -> int:
    """Scores and the product with v over the selected pairs (2 x 2
    operations a pair and a lane), forward and backward, one layer."""
    return (lmshapes.PASSES * 4 * s["head_dim"] * s["heads"] * s["sequences"]
            * selected_pairs(s["seq_len"], s["index_topk"]))


def attention_step_flops(s: dict) -> int:
    """ONE step's attention proper, every layer."""
    return s["layers"] * attention_flops(s)


def indexer_flops(s: dict) -> int:
    """One layer's indexer: its three projections of every token (forward
    and backward), the index heads' scores of every causal pair forward
    and of the selected pairs backward (twice a forward's), and the
    divergence's target, every attention head's score of the selected
    pairs once."""
    lanes = s["index_heads"] * s["index_dim"]
    tokens = s["sequences"] * s["seq_len"]
    chosen = s["sequences"] * selected_pairs(s["seq_len"], s["index_topk"])
    causal = s["sequences"] * causal_pairs(s["seq_len"])
    projections = lmshapes.PASSES * tokens * 2 * s["hidden"] * (
        lanes + s["index_dim"] + s["index_heads"])
    return (projections + 2 * lanes * (causal + 2 * chosen)
            + 2 * s["head_dim"] * s["heads"] * chosen)


def dense_flops(s: dict) -> int:
    """The products every token goes through: the four attention
    projections and the router in each layer, and the head."""
    return lmshapes.dense_flops(s["sequences"] * s["seq_len"], s)


def step_flops(steps: int, assignments: int, s: dict) -> int:
    """Operations of ``steps`` steps whose layers saw ``assignments``
    assignments on held experts in all."""
    layers = attention_step_flops(s) + s["layers"] * indexer_flops(s)
    return (steps * (layers + dense_flops(s))
            + lmshapes.expert_flops(assignments, s["hidden"],
                                    s["expert_width"]))

"""Operations and bytes a language-model training step has to do, from
its sizes and from what the step's counters saw (benchmark/drivers/lm.py
fills ``ctx.shapes`` with the sizes; the counters are the trainer's).

What is counted is what the mathematics needs, once: products for the
(token, expert) assignments that fell on held experts and no padding row,
attention for the unmasked (query, key) pairs only, the backward pass at
twice the forward and the layer's recomputation not at all, Adam at 28
bytes a parameter. So a share of a peak computed from these can only
understate the device's work, and cannot pass 100% by counting padding,
masked or repeated work.
"""

ADAM_BYTES_PER_PARAMETER = 28   # read w, m, v, g; write w, m, v: float32
#: The scopes of the server's update programs (updater/engine.py, rules.py):
#: the delta's padding, the rule, the rows form's sort and sum of equal ids
#: and its row writes. Every table of a language-model cell is under Adam.
UPDATE_SCOPES = ("mv.update.rule", "mv.update.dedup", "mv.update.scatter_add",
                 "mv.update.pad")
PASSES = 3                      # forward, and a backward of twice its work
#: This family's row of lib/families.py: the counters ``step_flops`` counts
#: from, and the attention kernels' scopes.
COUNTERS = ("LM_STEP", "LM_HELD_ASSIGNMENTS")
ATTENTION_SCOPES = ("mv.lm.attn.full.kernel", "mv.lm.attn.window.kernel")


def attention_pairs(seq_len: int, window: int) -> int:
    """Unmasked (query, key) pairs of one head over one sequence: causal,
    and with a ``window`` only ``i - window < j <= i``."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_flops(sequences: int, seq_len: int, heads: int, head_dim: int,
                    window: int) -> int:
    """Scores and the product with v over the unmasked pairs (2 x 2
    operations a pair and a lane), forward and backward."""
    return (PASSES * 4 * head_dim * heads * sequences
            * attention_pairs(seq_len, window))


def expert_flops(assignments: int, hidden: int, width: int) -> int:
    """Gate, up and down products of ``assignments`` (token, expert)
    pairs, forward and backward."""
    return PASSES * assignments * 3 * 2 * hidden * width


def dense_flops(tokens: int, s: dict) -> int:
    """The products every token goes through: the four attention
    projections and the router in each layer, and the head."""
    q = s["heads"] * s["head_dim"]
    kv = s["kv_heads"] * s["head_dim"]
    layer = 2 * s["hidden"] * (2 * q + 2 * kv + s["router_outputs"])
    return PASSES * tokens * (s["layers"] * layer
                              + 2 * s["hidden"] * s["vocab"])


def attention_step_flops(s: dict) -> int:
    """ONE step's attention proper: every layer's, causal or under the
    window by ``window_layout``."""
    return sum(
        attention_flops(s["sequences"], s["seq_len"], s["heads"],
                        s["head_dim"], s["window"] if windowed else 0)
        for windowed in s["window_layout"])


def step_flops(steps: int, assignments: int, s: dict) -> int:
    """Operations of ``steps`` steps whose layers saw ``assignments``
    assignments on held experts in all."""
    return (steps * (attention_step_flops(s)
                     + dense_flops(s["sequences"] * s["seq_len"], s))
            + expert_flops(assignments, s["hidden"], s["expert_width"]))


def expert_bytes(steps: int, assignments: int, s: dict) -> int:
    """The least an expert layer moves: its weights read once a pass in
    bfloat16, their float32 gradients written, each assignment's input
    row read and output row written in bfloat16."""
    weights = s["layers"] * s["held"] * 3 * s["hidden"] * s["expert_width"]
    return (steps * weights * (PASSES * 2 + 4)
            + PASSES * assignments * 2 * s["hidden"] * 2)


def adam_bytes(steps: int, embedding_rows: int, s: dict) -> int:
    """Adam over every whole table each step, and over the embedding rows
    the steps named (``embedding_rows``, distinct a step, summed)."""
    whole = s["parameters"] - s["vocab"] * s["hidden"]
    return ADAM_BYTES_PER_PARAMETER * (steps * whole
                                       + embedding_rows * s["hidden"])


def share_of_peak(needed: float, seconds: float, peak_per_s: float) -> float:
    """Percent: the least time over the time taken."""
    return 100.0 * (needed / peak_per_s) / seconds


def window_counts(window, names):
    """The window's counts of the trainer's counters ``names``; None
    where the program has none of them (a parent that lacks them)."""
    found = [window.counters.get(n, {}).get("count", 0) for n in names]
    return found if all(found) else None


def scopes_seconds(obs, scopes):
    """Busiest chip's seconds in the traced window under any of
    ``scopes``, every program summed; None without a trace or without
    an operation under them."""
    if obs.trace is None or not obs.traced or not obs.traced.rounds:
        return None
    found = [by[s] for by in obs.trace["scopes"].values()
             for s in scopes if s in by]
    return sum(found) if found else None


def scopes_ms_per_step(obs, scopes):
    seconds = scopes_seconds(obs, scopes)
    return None if seconds is None else seconds * 1e3 / obs.traced.rounds

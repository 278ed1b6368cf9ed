"""The expert layers' share of their roofline, percent: the least time
the chip could take over the products of the assignments the traced
window's counter saw (`LM_HELD_ASSIGNMENTS`; the larger of operations
over the bf16 peak and least bytes over the HBM peak,
benchmark/lib/lmshapes.py), over the device time under `mv.lm.experts`,
which also holds the sort, the gather and the sum back to tokens and the
recomputed forward pass. Compute-bound at these sizes."""

from benchmark.lib import lmshapes


def read(obs):
    took = lmshapes.scopes_seconds(obs, ("mv.lm.experts",))
    counts = took and lmshapes.window_counts(
        obs.traced, ("LM_STEP", "LM_HELD_ASSIGNMENTS"))
    if not counts:
        return None
    s = obs.shapes
    least = max(
        lmshapes.expert_flops(counts[1], s["hidden"], s["expert_width"])
        / obs.peaks["bf16_flops_per_s"],
        lmshapes.expert_bytes(counts[0], counts[1], s)
        / obs.peaks["hbm_bytes_per_s"])
    return 100.0 * least / took

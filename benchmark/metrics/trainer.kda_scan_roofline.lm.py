"""The scan's share of its roofline, percent: the least time the delta
layers' recurrences need (benchmark/lib/kdashapes.py: the larger of the
recurrence's operations a position a head over the bf16 peak and the bytes
of q, k, g, v, beta in and o out over the memory's, forward and backward,
nothing made again) over the device time under `mv.lm.attn.kda.scan`.
Counted as the MODEL's work, whatever the chunk: a chunked form's extra
arithmetic and bytes read as time, so the share cannot pass 100%."""

from benchmark.lib import kdashapes, lmshapes


def read(obs):
    took = lmshapes.scopes_seconds(obs, ("mv.lm.attn.kda.scan",))
    if not took or "kda_heads" not in obs.shapes:
        return None
    s = obs.shapes
    layers = obs.traced.rounds * kdashapes.layers_of(s, "kda")
    least = max(
        layers * kdashapes.scan_flops(s) / obs.peaks["bf16_flops_per_s"],
        layers * kdashapes.scan_bytes(s) / obs.peaks["hbm_bytes_per_s"])
    return 100.0 * least / took

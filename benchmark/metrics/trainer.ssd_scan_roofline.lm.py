"""The state-space scan's share of its roofline, percent: the least time
the layers' scans need, their BYTES over the memory's peak
(benchmark/lib/ssdshapes.py `scan_bytes`: X in and Y out, B, C and dt, `3 x
4 x (2 H P + 2 N + H)` a position, forward and twice for backward, nothing
made again), over the device time under `mv.lm.attn.ssd.scan`.
Memory-bound: at the cell's sizes the bytes need 2.5 times the time that
the function's operations do at chunks of 256. Counted as the LAYER's
arrays, whatever the chunk and whatever implements the scan: the
within-chunk factor, the cumulative sums and the states between chunks
read as time, so the share cannot pass 100% and a kernel does not change
the count. None where the program has no such scope."""

from benchmark.lib import lmshapes, ssdshapes


def read(obs):
    took = lmshapes.scopes_seconds(obs, ("mv.lm.attn.ssd.scan",))
    if not took or "ssd_heads" not in obs.shapes:
        return None
    s = obs.shapes
    needed = (obs.traced.rounds * ssdshapes.layers_of(s, "ssd")
              * ssdshapes.scan_bytes(s))
    return lmshapes.share_of_peak(needed, took, obs.peaks["hbm_bytes_per_s"])

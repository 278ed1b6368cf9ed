"""The state-space layers' gate and norm's share of their roofline,
percent: the least time they need, their BYTES over the memory's peak
(benchmark/lib/ssdshapes.py `gate_bytes`: Y and z read and the product
written forward, three read and two written backward, `4 x 8 x H P` a
position, nothing made again), over the device time under
`mv.lm.attn.ssd.gate`. Memory-bound: a few operations an element. None
where the program has no such scope."""

from benchmark.lib import lmshapes, ssdshapes


def read(obs):
    took = lmshapes.scopes_seconds(obs, ("mv.lm.attn.ssd.gate",))
    if not took or "ssd_heads" not in obs.shapes:
        return None
    s = obs.shapes
    needed = (obs.traced.rounds * ssdshapes.layers_of(s, "ssd")
              * ssdshapes.gate_bytes(s))
    return lmshapes.share_of_peak(needed, took, obs.peaks["hbm_bytes_per_s"])

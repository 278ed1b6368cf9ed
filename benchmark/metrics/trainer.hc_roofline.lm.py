"""The stream mixers' share of the HBM-bandwidth bound, percent: each
sublayer's stream tensor `[tokens, 4 x 3584]` read once and written once a
pass in float32 as stored, two sublayers a layer, three passes
(benchmark/lib/mlashapes.py `stream_bytes`), over the device time under
`mv.lm.hc`. Bound by memory bandwidth: a few operations an element."""

from benchmark.lib import lmshapes, mlashapes


def read(obs):
    took = lmshapes.scopes_seconds(obs, ("mv.lm.hc",))
    if not took or "streams" not in obs.shapes:
        return None
    return lmshapes.share_of_peak(
        mlashapes.stream_bytes(obs.traced.rounds, obs.shapes), took,
        obs.peaks["hbm_bytes_per_s"])

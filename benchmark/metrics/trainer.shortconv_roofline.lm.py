"""The convolution layers' mixers' share of the bf16 peak, percent: the
operations of their two products (`W_in` [hidden, 3 hidden], `W_out`
[hidden, hidden]) and of the gates and taps between them, forward and twice
for backward (benchmark/lib/convshapes.py; the recomputed forward pass is not
counted), over the device time under `mv.lm.attn.shortconv` and
`mv.lm.attn.shortconv.taps` TOGETHER. Together because the compiler fuses most
of the elementwise chain into the products' own fusions, which carry the
products' scope: the `.taps` scope alone holds a tenth of the mixers' time,
and a share of the chain's bytes over it would leave out most of the work it
counts (PERF.md section 6, PR 63). Compute-bound: the products' operations
need three times the time their bytes do. None where the program has no such
scope."""

from benchmark.lib import convshapes, lmshapes

SCOPES = ("mv.lm.attn.shortconv", "mv.lm.attn.shortconv.taps")


def read(obs):
    took = lmshapes.scopes_seconds(obs, SCOPES)
    if not took or "conv_taps" not in obs.shapes:
        return None
    s = obs.shapes
    flops = (obs.traced.rounds * convshapes.layers_of(s, "conv")
             * convshapes.mixer_flops(s))
    return lmshapes.share_of_peak(flops, took, obs.peaks["bf16_flops_per_s"])

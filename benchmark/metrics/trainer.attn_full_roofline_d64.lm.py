"""The attention layers' kernel's share of the bf16 peak at heads of 64
lanes, half a tile, percent: the causal (query, key) pairs x `2 (64 + 64)`
operations a pair x the query heads, forward and twice for backward
(benchmark/lib/convshapes.py; the recomputed forward pass and the kernel's
own recomputation of the scores are not counted), over the device time
under `mv.lm.attn.full.kernel`. The kernel is the library's splash
attention, a group a key-value head, handed the heads at their own 64
lanes. Compute-bound; a product of 64 lanes fills half of the unit's
width, so the share reads about half of what the same kernel reads at 128
lanes."""

from benchmark.lib import convshapes, lmshapes


def read(obs):
    took = lmshapes.scopes_seconds(obs, ("mv.lm.attn.full.kernel",))
    if not took or "conv_taps" not in obs.shapes:
        return None
    s = obs.shapes
    flops = (obs.traced.rounds * convshapes.layers_of(s, "gqa")
             * convshapes.attention_flops(s))
    return lmshapes.share_of_peak(flops, took, obs.peaks["bf16_flops_per_s"])

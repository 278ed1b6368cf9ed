"""The softmax layers' attention kernel's share of the bf16 peak where a
chip holds a SHARE of a layer's heads, percent: the causal (query, key)
pairs x `2 (128 + 128)` operations a pair x the HELD query heads, forward
and twice for backward (benchmark/lib/solarshapes.py; the recomputed forward
pass and the kernel's own recomputation of the scores are not counted), over
the device time under `mv.lm.attn.full.kernel`. The kernel is the library's
splash attention, a group a held key-value head. Compute-bound."""

from benchmark.lib import lmshapes, solarshapes


def read(obs):
    took = lmshapes.scopes_seconds(obs, ("mv.lm.attn.full.kernel",))
    if not took or "heads_all" not in obs.shapes:
        return None
    s = obs.shapes
    flops = (obs.traced.rounds * solarshapes.layers_of(s, "gqa")
             * solarshapes.attention_flops(s))
    return lmshapes.share_of_peak(flops, took, obs.peaks["bf16_flops_per_s"])

"""The attention kernels' share of the bf16 peak, percent: the operations
of the unmasked (or selected) (query, key) pairs alone, forward and twice
for backward (the recomputed forward pass and the kernel's own
recomputation of the scores are not counted), over the device time under
the family's kernel scopes. ONE reader for every family of language model
with an attention kernel: benchmark/lib/families.py gives the counting
module of the family the cell's driver names, whose `ATTENTION_SCOPES` are
the scopes (`mv.lm.attn.full.kernel`, `.window.kernel`,
`.blockdiff.kernel`, `.mla.kernel`, `.sparse.kernel`) and whose
`attention_step_flops` counts one step's pairs at the family's own lanes,
heads (the HELD ones where a chip holds a share), masks and layers, from the
model's work whatever implements it. The kernel is the library's splash
attention (the sparse family's under a dynamic mask). Compute-bound; a head of
64 lanes fills half of the unit's width and reads about half of what 128
lanes read. Until PR 67 each family had an entry of its own
(`trainer.attn_blockdiff_roofline.lm`, `attn_mla_roofline`,
`attn_mixed_roofline`, `attn_sparse_roofline`, `attn_full_roofline_held`,
`attn_full_roofline_d64`): their histories continue here. None for shapes
of no family, a family without kernel scopes, or a trace without them."""

from benchmark.lib import families, lmshapes


def read(obs):
    family = families.counting(obs.shapes)
    scopes = getattr(family, "ATTENTION_SCOPES", None)
    if not scopes:
        return None
    took = lmshapes.scopes_seconds(obs, scopes)
    if not took:
        return None
    flops = obs.traced.rounds * family.attention_step_flops(obs.shapes)
    return lmshapes.share_of_peak(flops, took, obs.peaks["bf16_flops_per_s"])

"""Percent of the window's LATENT layer-sequences whose way from the
attention's products to the kernel and back was the ONE pass of
`models/lm/latent_kernels.py` (the rotary turn of a head's last lanes and
of the shared key, the joins `[nope | rope]`, the split of `[k_n | v]`,
scale, rounding and the kernel's layout read once and written once, each
way) and not the `jax.numpy` chain of `latent.inputs`: counters
`LM_ATTN_PASS_FUSED` over `LM_ATTN_PASS_FUSED` + `LM_ATTN_PASS_PLAIN` (one
a latent layer a sequence, the multi-token module's layer too,
`PSLMTrainer._count_stats`, by the test `latent.inputs` chose by:
`latent.pass_fused`), measured window. 100 on a TPU at whole blocks of 512
tokens where `nope + rope` and `v` are each whole 128-lane tiles and the
layer is turned (`glm30b.ps-8k`); 0 at heads of 192 lanes
(`xing29b.ps-4k`) and in a layer without positions (`kimi48b.ps-8k`); a
program whose trainer counts neither under latent attention (the parent
commit of PR 57) has no such counter: nothing, then. The same pair of
counters that `trainer.attn_pass_fused_share.lm` reads in the cells of the
other attention: a cell has one kind or the other."""

from benchmark.lib import counters


def read(obs):
    return counters.share(obs.window.counters, "LM_ATTN_PASS_FUSED",
                          "LM_ATTN_PASS_PLAIN")

"""Percent of the window's layer-sequences whose attention's way from the
projections to the kernel and back was the ONE pass of
`models/lm/attn_kernels.py` (heads' norm, rotary turn, scale, rounding and
the kernel's layout read once and written once, each way) and not the
`jax.numpy` chain: counters `LM_ATTN_PASS_FUSED` over `LM_ATTN_PASS_FUSED`
+ `LM_ATTN_PASS_PLAIN` (one a layer a sequence, `PSLMTrainer._count_stats`,
by the test `model.attention_inputs` chose by), measured window. 100 on a
TPU at whole blocks of 512 tokens and heads of whole 128-lane tiles; a
trainer under latent attention counts neither, and a program without the
pass (the parent commit) has no such counter: nothing, then."""

from benchmark.lib import counters


def read(obs):
    return counters.share(obs.window.counters, "LM_ATTN_PASS_FUSED",
                          "LM_ATTN_PASS_PLAIN")

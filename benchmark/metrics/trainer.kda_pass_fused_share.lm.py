"""Percent of the window's delta layers' sequences whose gates (the two L2
norms a head, the scale on q, the log decay, beta) and gated output norm
ran as the Pallas passes of `models/lm/delta_passes.py`, one over memory
each way, and not as the `jax.numpy` chain of `delta.gates` and
`delta.output`: counters `LM_KDA_PASS_FUSED` over `LM_KDA_PASS_FUSED` +
`LM_KDA_PASS_PLAIN` (one a delta layer a sequence,
`PSLMTrainer._count_stats`, by the test `delta.attention_vjp` chose by:
`delta.passes_fused`), measured window. 100 on a TPU at whole blocks of 512
tokens and heads of one 128-lane tile; a program without the passes (the
parent commit) has no such counter: nothing, then."""

from benchmark.lib import counters


def read(obs):
    return counters.share(obs.window.counters, "LM_KDA_PASS_FUSED",
                          "LM_KDA_PASS_PLAIN")

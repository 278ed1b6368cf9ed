"""Percent of the window's delta layers' sequences whose scan ran as the
Pallas kernels of `models/lm/delta_kernels.py` (a head's chunks walked with
the state and the chunk's matrices in fast memory, forward and backward)
and not as the `jax.numpy` runs of chunks: counters `LM_KDA_SCAN_KERNEL`
over `LM_KDA_SCAN_KERNEL` + `LM_KDA_SCAN_PLAIN` (one a delta layer a
sequence, `PSLMTrainer._count_stats`, by the test `delta.scan` chose by),
measured window. 100 on a TPU at chunks of 64 and heads of one 128-lane
tile; a program without the kernels (the parent commit) has no such
counter: nothing, then."""

from benchmark.lib import counters


def read(obs):
    return counters.share(obs.window.counters, "LM_KDA_SCAN_KERNEL",
                          "LM_KDA_SCAN_PLAIN")

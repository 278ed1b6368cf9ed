"""Percent of the window's state-space layers' sequences whose scan ran as
the Pallas kernels of `models/lm/ssd_kernels.py` (a head group's chunks
walked with the within-chunk factor and the state in fast memory, forward
and backward) and not as the `jax.numpy` runs of chunks: counters
`LM_SSD_SCAN_KERNEL` over `LM_SSD_SCAN_KERNEL` + `LM_SSD_SCAN_PLAIN` (one a
state-space layer a sequence, by the test `ssd.scan` chose by), measured
window. 100 on a TPU at the cell's shapes; a program without the kernels
has neither counter: nothing, then."""

from benchmark.lib import counters


def read(obs):
    return counters.share(obs.window.counters, "LM_SSD_SCAN_KERNEL",
                          "LM_SSD_SCAN_PLAIN")

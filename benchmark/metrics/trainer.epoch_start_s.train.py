"""Seconds from a train_epoch call to its first block done, median over
the epochs begun in the measured window: the epoch's preparation
(`_prep`, the subsampling argsort of the whole corpus), the readback of
the kept count, `_pad`, and one block. Host clock; the first block's own
few milliseconds are in it."""

from benchmark.lib import stats


def read(obs):
    starts = obs.window.samples.get("epoch_start_ms")
    return stats.median(starts) / 1e3 if starts else None

"""Seconds an epoch's preparation took inside the trainer (Dashboard
TRAINER_EPOCH_PREP over its count: `train_epoch`'s entry to its first
block's dispatch, so `_prep`, `_pad` and the readback of the kept count;
measured window, profiler off). The driver's log line "call to first
block" times the same from outside, with the first block in it."""

from benchmark.lib import counters


MONITORS = ('TRAINER_EPOCH_PREP',)


def read(obs):
    ms = counters.ms_per_request(obs.window.counters, MONITORS)
    return None if ms is None else ms / 1e3

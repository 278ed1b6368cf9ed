"""Milliseconds of one device-to-host copy of a reply (Dashboard BLOB_D2H
over its count, measured window, profiler off): `np.asarray(jax.Array)`
in `Blob._host`, which waits for the gather that produces the array and
then copies it into a fresh host buffer."""

from benchmark.lib import counters


MONITORS = ('BLOB_D2H',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

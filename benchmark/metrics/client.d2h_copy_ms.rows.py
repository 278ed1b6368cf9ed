"""Milliseconds of one reply's copy device to host once the array is ready
(Dashboard BLOB_D2H_COPY over its count: `np.asarray` inside `BLOB_D2H`;
measured window, profiler off). With `client.d2h_wait_ms.rows` it is
`client.d2h_ms.rows`."""

from benchmark.lib import counters


MONITORS = ('BLOB_D2H_COPY',)


def read(obs):
    return counters.ms_per_request(obs.window.counters, MONITORS)

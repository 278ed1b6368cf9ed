"""Percent of the host deltas that entered `pad_rows` which were copied
into a staging buffer the engine keeps: Dashboard UPDATE_PAD_STAGED over
UPDATE_PAD_STAGED + UPDATE_PAD_FRESH (one a host delta `updater/engine.py`
`pad_rows` padded: into the head of a kept bucket-sized array, or into a
fresh one because the padded delta is small or every buffer of its bucket
is still being read), measured window. Under 100 a host Add has left the
form its time was measured on."""

from benchmark.lib import counters


def read(obs):
    return counters.share(obs.window.counters, "UPDATE_PAD_STAGED",
                          "UPDATE_PAD_FRESH")

"""Percent of the window's row scatter-adds that took the kernel's path:
Dashboard UPDATE_ROWS_FAST over UPDATE_ROWS_FAST + UPDATE_ROWS_XLA (one a
table's Add dispatch; two a block of the local trainer), measured
window. Under 100 a cell has left the path its rate was measured on."""

from benchmark.lib import counters


def read(obs):
    return counters.share(obs.window.counters, "UPDATE_ROWS_FAST",
                          "UPDATE_ROWS_XLA")

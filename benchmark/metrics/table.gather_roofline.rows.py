"""The table's gather program (a Get): percent of the HBM-bandwidth bound. The least
time the chip could take to move the bytes the request needs (from its
shapes, benchmark/lib/shapes.py, at the table's logical width) over the
program's device time in the trace. Bound by memory bandwidth: a row
gather does no arithmetic to speak of."""

from benchmark.lib import shapes
from benchmark.lib import tableprograms as tp


def read(obs):
    if obs.trace is None:
        return None
    took = tp.seconds(obs.trace, (tp.GATHER,))
    # requests of the round's size, from the caller's own count: the
    # one-row Get that closes the window runs the same stem
    count = len(obs.traced.samples.get("get_ms", []))
    if not count or not took:
        return None
    needed = count * shapes.gather_bytes(
        obs.shapes["rows_per_request"], obs.shapes["cols"],
        obs.shapes["value_bytes"])
    return shapes.roofline_share(needed, took,
                                 obs.peaks["hbm_bytes_per_s"])

"""Device milliseconds per Get+Add round of the table's gather and
scatter-add programs, busiest chip, traced window."""

from benchmark.lib import tableprograms as tp


STEMS = (tp.GATHER, tp.SCATTER_ADD)


def read(obs):
    if obs.trace is None or not obs.traced.rounds:
        return None
    return tp.seconds(obs.trace, STEMS) * 1e3 / obs.traced.rounds

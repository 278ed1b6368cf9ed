"""Device milliseconds per block of the programs that touch table rows,
busiest chip, traced window: the table's gather and scatter-add programs
in the PS cells; in the local cell the trainer's one group program,
which holds gather, step and scatter-add together."""

from benchmark.lib import tableprograms as tp


STEMS = (tp.GATHER, tp.SCATTER_ADD, tp.LOCAL_GROUP)


def read(obs):
    if obs.trace is None or not obs.traced.rounds:
        return None
    return tp.seconds(obs.trace, STEMS) * 1e3 / obs.traced.rounds

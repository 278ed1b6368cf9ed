"""Device milliseconds a step in the server's update programs: the rule
(`mv.update.rule`: Adam's arithmetic, dense or on the gathered rows), the
rows form's sort and sum of equal ids (`mv.update.dedup`) and its row
writes (`mv.update.scatter_add`), the delta's padding (`mv.update.pad`);
busiest chip, traced window. Every table of this cell is under Adam."""

from benchmark.lib import lmshapes

SCOPES = lmshapes.UPDATE_SCOPES


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, SCOPES)

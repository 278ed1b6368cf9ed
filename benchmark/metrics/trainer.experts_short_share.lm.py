"""Percent of the window's sparse layers' sequences whose routed experts
worked in the SHORT buffer (`model.experts_capacity` rows: twice the even
share of a sequence's assignments) and not in the one of every assignment:
counters `LM_EXPERTS_SHORT` over `LM_EXPERTS_SHORT` + `LM_EXPERTS_FULL`
(one a sparse layer a sequence, `PSLMTrainer._count_stats`, from the count
the device chooses by), measured window. Under 100 some sequences paid
for the full buffer; under 90 the capacity is too small for the cell's
routing."""

from benchmark.lib import counters


def read(obs):
    return counters.share(obs.window.counters, "LM_EXPERTS_SHORT",
                          "LM_EXPERTS_FULL")

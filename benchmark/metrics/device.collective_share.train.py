"""Percent of the traced window in which a collective operation ran on the
busiest chip (union of their intervals on `XLA Ops`)."""


def read(obs):
    if obs.trace is None:
        return None
    return 100.0 * obs.trace["collective_s"] / obs.trace["window_s"]

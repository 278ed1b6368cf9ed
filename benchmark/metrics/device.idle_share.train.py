"""Percent of the traced window in which no operation ran on the device,
averaged over the chips used."""


def read(obs):
    if obs.trace is None:
        return None
    return 100.0 * (1.0 - obs.trace["busy_s"] / obs.trace["window_s"])

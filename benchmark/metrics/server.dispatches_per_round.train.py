"""Device programs launched (events on the busiest chip's `XLA Modules`
line) per block or per Get+Add round, traced window."""


def read(obs):
    if obs.trace is None or not obs.traced.rounds:
        return None
    return obs.trace["launches"] / obs.traced.rounds

"""Readers of the monitors on the caller's thread and inside the two
wide spans (PR 37), beside ``counters.py``'s: milliseconds a ROUND (a
block, a step, a Get+Add round), where several monitors count different
things and only the round is common to them."""


def ms_per_round(obs, names):
    """The window's milliseconds under ``names`` over its rounds; None
    where none of them counted (a program without these monitors) or
    the window holds no round."""
    found = [obs.window.counters[n] for n in names
             if obs.window.counters.get(n, {}).get("count")]
    if not found or not obs.window.rounds:
        return None
    return sum(m["ms"] for m in found) / obs.window.rounds

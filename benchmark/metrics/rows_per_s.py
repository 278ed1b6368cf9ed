"""Rows returned by acknowledged Gets plus rows applied by acknowledged
Adds, over the window, which is closed by a sync on the table."""


def read(obs):
    rows = obs.window.work.get("rows")
    return None if rows is None else rows / obs.window.seconds

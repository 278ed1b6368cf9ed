"""Seconds set-up spent in the backend stage of programs that XLA compiled
(Dashboard PROGRAM_COMPILE's milliseconds as they stood when the measured
window opened): the cache key and the compile, one entry a program; its
count is `setup.programs_compiled`. 0.0 where the program listens and
every program came from the cache; None from a program that does not
listen (before PR 68)."""


def read(obs):
    stage = obs.window.at_open.get("PROGRAM_COMPILE")
    return None if stage is None else stage["elapsed_ms"] / 1e3

"""Seconds set-up spent in the backend stage of programs that the
persistent compile cache supplied (Dashboard PROGRAM_CACHE_READ's
milliseconds as they stood when the measured window opened): the cache
key, the retrieval and the load, one entry a program; its count is the
harness's `from_persistent_cache`. 0.0 where the program listens and the
cache supplied nothing; None from a program that does not listen (before
PR 68)."""


def read(obs):
    stage = obs.window.at_open.get("PROGRAM_CACHE_READ")
    return None if stage is None else stage["elapsed_ms"] / 1e3

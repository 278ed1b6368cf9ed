"""Seconds set-up spent tracing programs to jaxprs (Dashboard PROGRAM_TRACE's
milliseconds as they stood when the measured window opened): the
OUTERMOST trace of each program alone, the jitted functions traced inside
it included, as `util/dashboard.py`'s listeners to `jax.monitoring` count
them. With `setup.lower_s`, `setup.cache_read_s` and `setup.compile_s` it
is exclusive: the four sum to the THREAD-seconds spent making programs
(the server actor's thread builds beside the trainer's, so a process that
compiles can read more than `setup.build_s + setup.warm_s`). None from a
program that does not listen (before PR 68)."""


def read(obs):
    stage = obs.window.at_open.get("PROGRAM_TRACE")
    return None if stage is None else stage["elapsed_ms"] / 1e3

"""Milliseconds of stall the measured window held over the window's
seconds (Dashboard HOST_STALL's ms, counted at the beat that saw each
stretch; profiler off): every slow stretch the program's heartbeat
recorded (`runtime/thread_roles.py`; docs/OBSERVABILITY.md "Stalls"),
frozen, held or blocked. 5.6 is one stall of 0.112 s in a window of 20 s;
0.0 a window that held none; nothing where no heartbeat counted a beat (a
program without the sampler)."""


def read(obs):
    window = obs.window
    if not window.counters.get("HOST_BEAT_LATE", {}).get("count"):
        return None
    return window.counters.get("HOST_STALL", {}).get("ms", 0.0) \
        / window.seconds

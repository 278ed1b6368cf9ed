"""Milliseconds of FROZEN stall the measured window held over the
window's seconds (Dashboard HOST_STALL_FROZEN's ms; profiler off): the
heartbeat woke late while the process used next to no CPU, so
the whole process was off the cores (a cgroup out of quota, a hypervisor,
a SIGSTOP): the part of `host.stall_ms_per_s` that is the machine's and
no PR's. Nothing where no heartbeat counted a beat."""


def read(obs):
    window = obs.window
    if not window.counters.get("HOST_BEAT_LATE", {}).get("count"):
        return None
    return window.counters.get("HOST_STALL_FROZEN", {}).get("ms", 0.0) \
        / window.seconds

"""GB/s of the replies' device-to-host copies: the bytes they moved
(Dashboard BLOB_D2H_BYTES, the arrays' `nbytes`) over the time they took
(BLOB_D2H), measured window, profiler off. The time includes the wait
for the gather that produces the array."""


def read(obs):
    copied = obs.window.counters.get("BLOB_D2H", {})
    moved = obs.window.counters.get("BLOB_D2H_BYTES", {})
    if not copied.get("count") or not copied.get("ms"):
        return None
    return moved.get("count", 0) / (copied["ms"] * 1e-3) / 1e9

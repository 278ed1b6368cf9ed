"""Seconds set-up spent drawing the tables' random rows on the device
(Dashboard TABLE_INIT's milliseconds as they stood when the measured
window opened: `MatrixServer`'s `random_init`, one draw a table). The
monitor spans the init program's lowering too, and in a checkout's first
run its compilation (8.4 s there against 0.56 from the compile cache)."""


def read(obs):
    drawn = obs.window.at_open.get("TABLE_INIT", {})
    if not drawn.get("count"):
        return None
    return drawn["elapsed_ms"] / 1e3

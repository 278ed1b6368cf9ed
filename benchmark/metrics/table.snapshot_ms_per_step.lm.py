"""Device milliseconds a step in the whole-table Gets' snapshot programs:
the fresh copy a whole-table Get replies with (the live array is donated
to the next update), 42 of them a step. The programs are the tables'
`snapshot` functions (stem `jit_snapshot`, scope `mv.table.snapshot`); a
copy the compiler places carries no scope, so the program's own time is
read; busiest chip, traced window."""

STEM = "jit_snapshot"


def read(obs):
    if obs.trace is None or not obs.traced or not obs.traced.rounds:
        return None
    program = obs.trace.get("programs", {}).get(STEM)
    if not program or not program["seconds"]:
        return None
    return program["seconds"] * 1e3 / obs.traced.rounds

"""Milliseconds a block the trainer's thread spent blocked in
WorkerTable.wait (Dashboard TABLE_WAIT's milliseconds over the window's
blocks, measured window, profiler off): a block waits for its two Gets.
Against `trainer.block_ms.train` it says whether the trainer mostly
waits for the tables or mostly dispatches."""


def read(obs):
    waited = obs.window.counters.get("TABLE_WAIT", {})
    if not waited.get("count") or not obs.window.rounds:
        return None
    return waited["ms"] / obs.window.rounds

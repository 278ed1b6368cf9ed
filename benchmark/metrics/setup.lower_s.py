"""Seconds set-up spent lowering modules to MLIR (Dashboard PROGRAM_LOWER's
milliseconds as they stood when the measured window opened): the Pallas
kernels' lowering to Mosaic and the tracing of their bodies are inside
it, one entry a module. None from a program that does not listen (before
PR 68)."""


def read(obs):
    stage = obs.window.at_open.get("PROGRAM_LOWER")
    return None if stage is None else stage["elapsed_ms"] / 1e3

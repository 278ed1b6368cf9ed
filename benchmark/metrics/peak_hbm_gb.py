"""Largest peak_bytes_in_use over the cell's devices after the window, in
GB (1e9 bytes): what decides how large a table a chip can serve."""


def read(obs):
    peak = obs.device["memory_peak_bytes"]
    return peak / 1e9 if peak else None

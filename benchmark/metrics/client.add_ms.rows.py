"""Median of one add_rows as the caller sees it, measured window."""

from benchmark.lib.stats import median


def read(obs):
    return median(obs.window.samples.get("add_ms", []))

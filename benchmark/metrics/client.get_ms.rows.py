"""Median of one get_rows as the caller sees it, measured window."""

from benchmark.lib.stats import median


def read(obs):
    return median(obs.window.samples.get("get_ms", []))

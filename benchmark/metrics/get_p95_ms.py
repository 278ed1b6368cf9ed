"""95th percentile (nearest rank) of one get_rows as the caller sees it:
request made to values in the caller's numpy buffer. Every Get of the
window is a sample."""

from benchmark.lib.stats import percentile


def read(obs):
    return percentile(obs.window.samples.get("get_ms", []), 95)

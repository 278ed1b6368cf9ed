"""The few statistics the metrics need, written out so that no later
change to a library's defaults moves a number."""

import math
import statistics


def percentile(values, p: float):
    """Nearest-rank percentile (p in 0..100); None for no samples."""
    if not values:
        return None
    data = sorted(values)
    rank = max(1, math.ceil(p / 100.0 * len(data)))
    return data[rank - 1]


def median(values):
    return statistics.median(values) if values else None

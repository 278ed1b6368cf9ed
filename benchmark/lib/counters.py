"""The program's Dashboard monitors, read as what they are: counts and
host milliseconds inside the actors' handlers (they time the enqueue,
not the device)."""


def snapshot() -> dict:
    from multiverso_tpu.util import dashboard
    return dashboard.metrics_snapshot(max_samples=0)["monitors"]


def delta(before: dict, after: dict) -> dict:
    """``{name: {"count": n, "ms": elapsed}}`` over an interval."""
    out = {}
    for name, now in after.items():
        was = before.get(name, {"count": 0, "elapsed_ms": 0.0})
        out[name] = {"count": now["count"] - was["count"],
                     "ms": now["elapsed_ms"] - was["elapsed_ms"]}
    return out


def ms_per_request(counters: dict, names) -> float:
    """Milliseconds over requests, summed over ``names``; None where no
    request was counted."""
    count = sum(counters.get(n, {}).get("count", 0) for n in names)
    if not count:
        return None
    return sum(counters.get(n, {}).get("ms", 0.0) for n in names) / count


def share(counters: dict, name: str, other: str) -> float:
    """Percent of ``name``'s count in the two counts together; None where
    neither counted."""
    mine = counters.get(name, {}).get("count", 0)
    both = mine + counters.get(other, {}).get("count", 0)
    return 100.0 * mine / both if both else None

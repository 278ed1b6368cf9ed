"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read. Kept with the benchmark so that every PR reduces a trace
in the same way; checked against a small recorded trace in
``benchmark/tests/``.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per program
launched, named ``<jit name>(<hash>)``) and a line ``XLA Ops`` (one
event per operation inside it); and ``/host:CPU`` with one line per
thread, on which ``jax.profiler.TraceAnnotation`` spans appear under
their own names. All on one clock, in nanoseconds.

Busy time is the union of the intervals in which an operation ran on a
chip, cut to the window span. Idle gaps are what is left of the window
on the busiest chip, each named by the harness's span (``bench:<call>``)
that covers most of it.
"""

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
NO_SPAN = "no-span"

_HASH = re.compile(r"[(_]\d{5,}[)_]?$")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute|collective-broadcast|send|recv)", re.I)


def stem(name: str) -> str:
    """``jit_rows_padded(15895034004113943741)`` -> ``jit_rows_padded``.
    The hash changes with every change to the program; the stem is what
    a metric can key on until the program names its scopes."""
    return _HASH.sub("", name).rstrip("_(")


def is_collective(op_name: str) -> bool:
    return bool(_COLLECTIVE.match(op_name.lstrip("%")))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """The trace as plain lists: ``{"devices": {plane: {"modules": [(name,
    start_ns, end_ns)], "ops": [...]}}, "spans": [(name, start, end)]}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULE_LINE, OP_LINE):
                    lines[line.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            devices[plane.name] = {"modules": lines.get(MODULE_LINE, []),
                                   "ops": lines.get(OP_LINE, [])}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return {"devices": devices, "spans": spans}


def _clip(events, lo, hi):
    out = []
    for name, a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _union(intervals):
    """Sorted, merged [a, b) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def _length(merged) -> float:
    return sum(b - a for a, b in merged)


def _name_gap(a, b, spans) -> str:
    best, best_overlap = NO_SPAN, 0
    for name, s, e in spans:
        overlap = min(b, e) - max(a, s)
        if overlap > best_overlap:
            best, best_overlap = name[len(SPAN_PREFIX):], overlap
    return best


def reduce(trace: dict) -> dict:
    """See the module's docstring. Seconds throughout. Returns None when
    the trace holds no device plane (a CPU run)."""
    devices = trace["devices"]
    if not devices:
        return None
    windows = [(s, e) for name, s, e in trace["spans"]
               if name == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        every = [t for d in devices.values() for _, a, b in d["ops"]
                 for t in (a, b)]
        lo, hi = min(every), max(every)
    spans = [x for x in trace["spans"] if x[0] != WINDOW_SPAN]

    per_device = {}
    for plane, lines in devices.items():
        ops = _clip(lines["ops"] or lines["modules"], lo, hi)
        busy = _union((a, b) for _, a, b in ops)
        programs = {}
        for name, a, b in _clip(lines["modules"], lo, hi):
            slot = programs.setdefault(stem(name), [0.0, 0])
            slot[0] += (b - a) * 1e-9
            slot[1] += 1
        collective = _union((a, b) for name, a, b in ops
                            if is_collective(name))
        per_device[plane] = {
            "busy": busy, "busy_s": _length(busy) * 1e-9,
            "programs": {k: {"seconds": v[0], "count": v[1]}
                         for k, v in programs.items()},
            "launches": sum(v[1] for v in programs.values()),
            "collective_s": _length(collective) * 1e-9}

    busiest = max(per_device, key=lambda p: per_device[p]["busy_s"])
    top = per_device[busiest]
    gaps, edge = [], lo
    for a, b in top["busy"] + [[hi, hi]]:
        if a > edge:
            gaps.append((_name_gap(edge, a, spans), (a - edge) * 1e-9))
        edge = max(edge, b)
    gaps.sort(key=lambda g: -g[1])
    totals = {}
    for name, seconds in gaps:
        totals[name] = totals.get(name, 0.0) + seconds
    return {
        "window_s": (hi - lo) * 1e-9,
        "device_count": len(per_device),
        "busy_s": sum(d["busy_s"] for d in per_device.values())
        / len(per_device),
        "busy_s_by_device": {p: d["busy_s"] for p, d in per_device.items()},
        "busiest": busiest,
        "programs": top["programs"],
        "launches": top["launches"],
        "collective_s": top["collective_s"],
        "gaps": gaps,
        "gap_totals": totals,
    }


def breakdown(reduced: dict) -> dict:
    """The ledger's ``breakdown``: at most ten device programs by time,
    the five longest idle gaps and the five largest totals by span."""
    ops = sorted(((name, p["seconds"])
                  for name, p in reduced["programs"].items()),
                 key=lambda x: -x[1])[:10]
    longest = [[name, s] for name, s in reduced["gaps"][:5]]
    totals = sorted(reduced["gap_totals"].items(), key=lambda x: -x[1])[:5]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": longest + [[f"all:{n}", s] for n, s in totals]}

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

The program names its own work too, and the reduction reads both names
(``lib/xspace.py`` reads them out of the file). Every Dashboard monitor
is an ``mv:<NAME>`` span on a host thread's line, so each idle gap is
also cut where such spans begin and end, and each piece goes to the
innermost span open over it on any thread: the shortest one, since spans
of one thread nest. A span that only waits (``WAITS``) loses to a
working span on another thread; a piece under no ``mv:`` span goes to
the ``bench:`` span that names its gap (``idle_by_span``). Every
operation carries its ``jax.named_scope`` path, so a program's device
time is split by the innermost scope that starts with ``mv.``
(``scopes``), an operation that encloses others (a loop) counting its
own time only. A trace with no ``mv:`` span and no scope path reduces to
what it did before these were read.
"""

import bisect
import functools
import glob
import os
import re

from benchmark.lib.xspace import (  # noqa: F401 - read by tools/ and tests
    DEVICE_PLANE, HOST_PLANE, MODULE_LINE, OP_LINE, load)

SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
NO_SPAN = "no-span"
MV_PREFIX = "mv:"
SCOPE_PREFIX = "mv."
NO_SCOPE = "no-scope"
# monitors that time a thread blocked on another's work
WAITS = ("mv:TABLE_WAIT", "mv:PS_GET_STALL", "mv:MA_COMM_STALL",
         "mv:BLOB_D2H_READY")

_HASH = re.compile(r"[(_]\d{5,}[)_]?$")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute|collective-broadcast|send|recv)", re.I)


@functools.lru_cache(maxsize=None)    # an event's name is its metadata's
def stem(name: str) -> str:
    """``jit_rows_padded(15895034004113943741)`` -> ``jit_rows_padded``.
    The hash changes with every change to the program; the stem is what
    a metric can key on until the program names its scopes."""
    return _HASH.sub("", name).rstrip("_(")


@functools.lru_cache(maxsize=None)
def is_collective(op_name: str) -> bool:
    return bool(_COLLECTIVE.match(op_name.lstrip("%")))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


@functools.lru_cache(maxsize=None)
def scope_of(path: str) -> str:
    """``jit(f)/mv.update.rule/mv.update.scatter_add/scatter-add:`` ->
    ``mv.update.scatter_add``."""
    ours = [part for part in path.split("/")
            if part.startswith(SCOPE_PREFIX)]
    return ours[-1] if ours else NO_SCOPE


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


def _by_scope(modules, ops, lo, hi):
    """One chip's operation time inside [lo, hi) as ``{program stem:
    {scope: seconds}}``, and the collective operations' part of it in the
    same form. An operation belongs to the program running when it
    starts, and one that encloses others (a loop) counts its own time
    only: what an enclosed one takes is taken off the encloser."""
    modules = sorted(modules, key=lambda m: m[1])
    begins = [m[1] for m in modules]
    scopes, crossing = {}, {}

    def clip(t):
        return min(max(t, lo), hi)

    def add(names, ns):
        program, scope, collective = names
        if not ns:
            return      # outside the window: no entry, not a zero
        for out in (scopes, crossing) if collective else (scopes,):
            slot = out.setdefault(program, {})
            slot[scope] = slot.get(scope, 0.0) + ns * 1e-9

    stack = []   # enclosing operations: (end, (stem, scope, is collective))
    for op in sorted(ops, key=lambda op: (op[1], -op[2])):
        name, a, b = op[:3]
        at = bisect.bisect_right(begins, a) - 1
        if at < 0 or modules[at][2] < a:
            continue     # no program encloses it
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            end, parent = stack[-1]
            add(parent, clip(a) - clip(min(b, end)))
        mine = (stem(modules[at][0]), scope_of(op[3] if len(op) > 3 else ""),
                is_collective(name))
        add(mine, clip(b) - clip(a))
        stack.append((b, mine))
    return scopes, crossing


def _cut_gaps(gaps, spans) -> list:
    """Each idle gap ``(a, b, name)`` cut where an ``mv:`` span begins or
    ends, one sweep over gaps and span edges together: ``[{name: ns}]``, a
    piece going to the innermost working span open over it (else to a
    waiting one, else to the gap's own ``name``)."""
    edges = sorted([(s, 0, i) for i, (_, s, _) in enumerate(spans)]
                   + [(e, 1, i) for i, (_, _, e) in enumerate(spans)])
    keys = [(name in WAITS, e - s, name) for name, s, e in spans]
    open_now, at, out = {}, 0, []
    for a, b, outer in gaps:
        pieces, edge = {}, a
        while True:
            upto = edges[at][0] if at < len(edges) else b
            if edge < min(upto, b):
                name = min(open_now.values())[2] if open_now else outer
                pieces[name] = pieces.get(name, 0) + min(upto, b) - edge
                edge = min(upto, b)
            if upto >= b:
                break
            _, closes, i = edges[at]
            if closes:
                open_now.pop(i, None)
            else:
                open_now[i] = keys[i]
            at += 1
        out.append(pieces)
    return out


def reduce(trace: dict) -> dict:
    """See the module's docstring. Seconds throughout. Returns None when
    the trace holds no device plane (a CPU run)."""
    devices = trace["devices"]
    if not devices:
        return None
    every = [span[:3] for span in trace["spans"]]
    windows = [(s, e) for name, s, e in every if name == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        times = [t for d in devices.values() for op in d["ops"]
                 for t in op[1:3]]
        lo, hi = min(times), max(times)
    spans = [x for x in every
             if x[0].startswith(SPAN_PREFIX) and x[0] != WINDOW_SPAN]
    ours = [x for x in every if x[0].startswith(MV_PREFIX)
            and x[2] > lo and x[1] < hi]

    per_device = {}
    for plane, lines in devices.items():
        ops = _clip([op[:3] for op in lines["ops"] or lines["modules"]],
                    lo, hi)
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
    scopes, crossing = _by_scope(devices[busiest]["modules"],
                                 devices[busiest]["ops"], lo, hi)
    idle, edge = [], lo
    for a, b in top["busy"] + [[hi, hi]]:
        if a > edge:
            idle.append((edge, a, _name_gap(edge, a, spans)))
        edge = max(edge, b)
    gaps = sorted(((name, (b - a) * 1e-9) for a, b, name in idle),
                  key=lambda g: -g[1])
    totals = {}
    for name, seconds in gaps:
        totals[name] = totals.get(name, 0.0) + seconds
    by_span, named = {}, []
    for (a, b, _), pieces in zip(idle, _cut_gaps(idle, ours)):
        for name, ns in pieces.items():
            by_span[name] = by_span.get(name, 0.0) + ns * 1e-9
        named.append((max(pieces, key=pieces.get), (b - a) * 1e-9))
    named.sort(key=lambda g: -g[1])
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
        # as the program names them (the module's docstring)
        "scopes": scopes,
        "collective_s_by_scope": crossing,
        "idle_by_span": by_span,
        "gaps_by_span": named,
    }


def scope_ms_per_round(obs, scope: str):
    """Milliseconds a traced round the busiest chip spent under ``scope``,
    over all its programs; None without a trace, a round or an operation
    that carries the scope."""
    if obs.trace is None or not obs.traced.rounds:
        return None
    found = [by[scope] for by in obs.trace["scopes"].values() if scope in by]
    return sum(found) * 1e3 / obs.traced.rounds if found else None


def breakdown(reduced: dict) -> dict:
    """The ledger's ``breakdown``: at most ten device programs by time, a
    program whose operations carry ``mv.`` scopes as ``<stem>/<scope>``;
    the five longest idle gaps, each named by the span most of it lies
    under, and the five largest totals of ``idle_by_span``."""
    ops = []
    for name, program in reduced["programs"].items():
        by = reduced["scopes"].get(name, {})
        if set(by) - {NO_SCOPE}:
            ops += [(f"{name}/{scope}", s) for scope, s in by.items()]
        else:
            ops.append((name, program["seconds"]))
    ops.sort(key=lambda x: -x[1])
    longest = [[name, s] for name, s in reduced["gaps_by_span"][:5]]
    totals = sorted(reduced["idle_by_span"].items(),
                    key=lambda x: -x[1])[:5]
    return {"device_ops": [[n, s] for n, s in ops[:10]],
            "idle_gaps": longest + [[f"all:{n}", s] for n, s in totals]}

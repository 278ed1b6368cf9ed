#!/usr/bin/env python3
"""Read the program's own spans and scopes out of a profiler trace:

    python3 tools/trace_spans.py <file.xplane.pb>

for a trace kept with ``benchmark/run.py --trace 1 --keep-trace <dir>``
or captured with ``multiverso_tpu.util.trace_to``. Three tables:

(i) The idle time of the busiest chip by what the host was doing. The
    window and the chip's idle gaps are ``benchmark/lib/xplane.py``'s.
    Every Dashboard monitor is an ``mv:<NAME>`` span on a host thread's
    line (util/dashboard.py), so each gap is cut where such spans begin
    and end, and each piece goes to the innermost span open over it on
    any thread: the shortest one, since spans of one thread nest. A span
    that only waits (``WAITS``) loses to a working span on another
    thread. A piece under no ``mv:`` span goes to the harness's
    ``bench:`` span that covers most of its gap, as ``xplane.reduce``
    names gaps; a trace without ``mv:`` spans so gives its totals.

(ii) Device seconds by named scope within each program (by the stem
    ``xplane.stem`` leaves of its name), over the whole trace and not
    only the window: an epoch's ``_prep`` runs before it. The scopes are
    the ``jax.named_scope`` names that start with ``mv.``; an operation
    under several is counted under the innermost. Beside each total,
    the part of it that collective operations took on the busiest chip
    (``xplane.is_collective``: all-reduce, all-gather, collective-permute
    and the rest, their ``-start`` and ``-done`` halves too): what a
    scope's work costs in crossing chips on a table laid over several.

(iii) With more than one chip, each chip's busy and collective seconds in
    the window: which chips a block's programs keep busy (the corpus
    and its `_prep` live on the first chip only, the tables' shards on
    all).

Where a scope's name lives (looked at on a v5e trace, PR 24): not on the
events of the ``XLA Ops`` line but on their metadata, in the stat
``tf_op``, as ``jit(_prep)/mv.prep.mask/gather:``. ``jax.profiler.
ProfileData`` shows an event's own stats only, so this file reads the
``.xplane.pb`` itself: the protobuf wire format of the six messages it
needs (xplane.proto), nothing else.
"""

import bisect
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark.lib import xplane  # noqa: E402

MV_PREFIX = "mv:"
SCOPE_PREFIX = "mv."
SCOPE_STAT = "tf_op"
WAITS = ("mv:TABLE_WAIT", "mv:PS_GET_STALL", "mv:MA_COMM_STALL")
NO_SPAN = xplane.NO_SPAN
NO_SCOPE = "no-scope"


# -- the file -----------------------------------------------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an xplane file")
            value = buf[i:i + size]
            i += size
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    """The value message of a ``map<int64, Message>`` entry."""
    for field, value in _fields(buf):
        if field == 2:
            return value
    return b""


def _named(buf, stats_field=None):
    """``(id, name, [XStat])`` of an XEventMetadata or XStatMetadata."""
    ident, name, stats = 0, "", []
    for field, value in _fields(buf):
        if field == 1:
            ident = value
        elif field == 2:
            name = _text(value)
        elif field == stats_field:
            stats.append(value)
    return ident, name, stats


def _plane(buf):
    """``(name, lines, {metadata id: (name, scope path)})``; a line is
    ``(id, name, [(metadata id, start_ns, end_ns)])``."""
    name, lines, events, stat_names = "", [], {}, {}
    for field, value in _fields(buf):
        if field == 2:
            name = _text(value)
        elif field == 3:
            lines.append(value)
        elif field == 4:
            ident, label, stats = _named(_map_entry(value), stats_field=5)
            events[ident] = (label, stats)
        elif field == 5:
            ident, label, _ = _named(_map_entry(value))
            stat_names[ident] = label
    metadata = {}
    for ident, (label, stats) in events.items():
        path = ""
        for stat in stats:
            fields = dict(_fields(stat))
            if stat_names.get(fields.get(1)) == SCOPE_STAT:
                path = _text(fields[5]) if 5 in fields \
                    else stat_names.get(fields.get(7), "")
        metadata[ident] = (label, path)
    return name, [_line(line) for line in lines], metadata


def _line(buf):
    ident, name, stamp_ns, raw = 0, "", 0, []
    for field, value in _fields(buf):
        if field == 1:
            ident = value
        elif field == 2:
            name = _text(value)
        elif field == 3:
            stamp_ns = value
        elif field == 4:
            raw.append(value)
    events = []
    for event in raw:
        fields = dict(_fields(event))
        # whole nanoseconds, as jax.profiler.ProfileData gives them: this
        # file and xplane.py then cut a trace at the same instants
        start = stamp_ns + fields.get(2, 0) // 1000
        events.append((fields.get(1, 0), start,
                       start + fields.get(3, 0) // 1000))
    return ident, name, events


def load(path: str) -> dict:
    """``{"devices": {plane: {"modules": [(name, start_ns, end_ns)],
    "ops": [(name, start_ns, end_ns, scope path)]}}, "spans": [(name,
    start_ns, end_ns, thread)]}``: the ``mv:`` and ``bench:`` spans of
    every host thread."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices, spans = {}, []
    for field, value in _fields(space):
        if field != 1:
            continue
        name, lines, metadata = _plane(value)
        if name.startswith(xplane.DEVICE_PLANE):
            found = {xplane.MODULE_LINE: [], xplane.OP_LINE: []}
            for _, line_name, events in lines:
                if line_name in found:
                    found[line_name] = [metadata[m] + (a, b)
                                        for m, a, b in events]
            devices[name] = {
                "modules": [(n, a, b) for n, _, a, b
                            in found[xplane.MODULE_LINE]],
                "ops": [(n, a, b, path) for n, path, a, b
                        in found[xplane.OP_LINE]]}
        elif name == xplane.HOST_PLANE:
            for thread, _, events in lines:
                for m, a, b in events:
                    label = metadata[m][0]
                    if label.startswith((MV_PREFIX, xplane.SPAN_PREFIX)):
                        spans.append((label, a, b, thread))
    return {"devices": devices, "spans": spans}


# -- (i) idle time by span ------------------------------------------------------

def _window(devices, spans):
    windows = [(a, b) for name, a, b, _ in spans
               if name == xplane.WINDOW_SPAN]
    if windows:
        return min(a for a, _ in windows), max(b for _, b in windows)
    every = [t for d in devices.values() for op in d["ops"]
             for t in op[1:3]]
    return min(every), max(every)


def _busy(lines, lo, hi):
    ops = lines["ops"] or lines["modules"]
    return xplane._union((a, b) for _, a, b in
                         xplane._clip([op[:3] for op in ops], lo, hi))


def _pieces(a, b, spans):
    """Cut the gap [a, b) where a span begins or ends: ``[(name or None,
    ns)]``, the name the innermost working span's, else a waiting one's."""
    edges = sorted({a, b} | {t for _, s, e in spans for t in (s, e)
                             if a < t < b})
    out = []
    for p, q in zip(edges, edges[1:]):
        over = [(name in WAITS, e - s, name) for name, s, e in spans
                if s <= p and e >= q]
        out.append((min(over)[2] if over else None, q - p))
    return out


def idle_by_span(devices, spans) -> dict:
    lo, hi = _window(devices, spans)
    busy_by_plane = {plane: _busy(lines, lo, hi)
                     for plane, lines in devices.items()}
    busiest = max(busy_by_plane,
                  key=lambda p: xplane._length(busy_by_plane[p]))
    bench = [(n, a, b) for n, a, b, _ in spans
             if n.startswith(xplane.SPAN_PREFIX) and n != xplane.WINDOW_SPAN]
    ours = sorted(((n, a, b) for n, a, b, _ in spans
                   if n.startswith(MV_PREFIX)), key=lambda s: s[1])
    starts = [s[1] for s in ours]
    longest = max((b - a for _, a, b in ours), default=0.0)
    totals, edge = {}, lo
    for a, b in busy_by_plane[busiest] + [[hi, hi]]:
        if a > edge:
            outer = xplane._name_gap(edge, a, bench)
            outer = outer if outer == NO_SPAN else xplane.SPAN_PREFIX + outer
            # spans that can reach into the gap: begun before its end,
            # and no earlier than the longest span before its start
            near = ours[bisect.bisect_left(starts, edge - longest):
                        bisect.bisect_left(starts, a)]
            for name, ns in _pieces(edge, a, [s for s in near
                                              if s[2] > edge]):
                name = name or outer
                totals[name] = totals.get(name, 0.0) + ns * 1e-9
        edge = max(edge, b)
    idle = sum(totals.values())
    named = sum(s for name, s in totals.items()
                if name.startswith(MV_PREFIX))
    return {"window_s": (hi - lo) * 1e-9, "busiest": busiest,
            "idle_s": idle, "mv_share": named / idle if idle else 0.0,
            "totals": totals}


# -- (ii) device time by scope ----------------------------------------------------

def scope_of(path: str) -> str:
    """``jit(f)/mv.update.rule/mv.update.scatter_add/scatter-add:`` ->
    ``mv.update.scatter_add``."""
    ours = [part for part in path.split("/")
            if part.startswith(SCOPE_PREFIX)]
    return ours[-1] if ours else NO_SCOPE


def _own_times(lines):
    """``(program stem, scope, operation, seconds)`` for every operation
    of one chip, over the whole trace. An operation that encloses others
    (a loop) counts its own time only: each enclosed one also yields its
    overlap, negated, under the encloser's names."""
    modules = sorted(lines["modules"], key=lambda m: m[1])
    begins = [m[1] for m in modules]
    stack = []   # enclosing operations: (end, stem, scope, name)
    for name, a, b, path in sorted(lines["ops"],
                                   key=lambda op: (op[1], -op[2])):
        at = bisect.bisect_right(begins, a) - 1
        if at < 0 or modules[at][2] < a:
            continue     # no program encloses it
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            end, *parent = stack[-1]
            yield (*parent, -(min(b, end) - a) * 1e-9)
        mine = (xplane.stem(modules[at][0]), scope_of(path), name)
        yield (*mine, (b - a) * 1e-9)
        stack.append((b, *mine))


def _add(out, stem, scope, seconds):
    slot = out.setdefault(stem, {})
    slot[scope] = slot.get(scope, 0.0) + seconds


def device_by_scope(devices) -> dict:
    """``{program stem: {scope: seconds}}`` summed over the chips."""
    out = {}
    for lines in devices.values():
        for stem, scope, _, seconds in _own_times(lines):
            _add(out, stem, scope, seconds)
    return out


def collectives_by_scope(lines) -> dict:
    """``{program stem: {scope: seconds}}`` of one chip's collective
    operations only."""
    out = {}
    for stem, scope, name, seconds in _own_times(lines):
        if xplane.is_collective(name):
            _add(out, stem, scope, seconds)
    return out


# -- (iii) each chip ------------------------------------------------------------

def by_chip(devices, spans) -> dict:
    """``{plane: {"busy_s", "collective_s"}}`` inside the window."""
    lo, hi = _window(devices, spans)
    out = {}
    for plane, lines in devices.items():
        collective = xplane._union(
            (a, b) for _, a, b in xplane._clip(
                [op[:3] for op in lines["ops"]
                 if xplane.is_collective(op[0])], lo, hi))
        out[plane] = {
            "busy_s": xplane._length(_busy(lines, lo, hi)) * 1e-9,
            "collective_s": xplane._length(collective) * 1e-9}
    return out


# -- all three ----------------------------------------------------------------

def report(devices, spans) -> dict:
    gaps = idle_by_span(devices, spans)
    return {"gaps": gaps, "scopes": device_by_scope(devices),
            "collectives": collectives_by_scope(devices[gaps["busiest"]]),
            "chips": by_chip(devices, spans)}


def read(path: str) -> dict:
    trace = load(path)
    if not trace["devices"]:
        raise SystemExit(f"{path} holds no {xplane.DEVICE_PLANE} plane")
    return report(trace["devices"], trace["spans"])


def render(found: dict) -> str:
    gaps = found["gaps"]
    lines = [f"window {gaps['window_s']:.3f} s, busiest chip "
             f"{gaps['busiest']}, idle {gaps['idle_s']:.3f} s, of it under "
             f"mv: spans {100 * gaps['mv_share']:.1f}%",
             "", "| idle time under | s | % of idle |", "| --- | --- | --- |"]
    for name, s in sorted(gaps["totals"].items(), key=lambda x: -x[1]):
        lines.append(f"| `{name}` | {s:.4f} | "
                     f"{100 * s / gaps['idle_s']:.1f} |")
    lines += ["", "| program | scope | device s | % of program | "
              "collectives on the busiest chip, s |",
              "| --- | --- | --- | --- | --- |"]
    programs = sorted(found["scopes"].items(),
                      key=lambda x: -sum(x[1].values()))
    for stem, scopes in programs:
        whole = sum(scopes.values())
        for scope, s in sorted(scopes.items(), key=lambda x: -x[1]):
            crossing = found["collectives"].get(stem, {}).get(scope, 0.0)
            lines.append(f"| `{stem}` | `{scope}` | {s:.4f} | "
                         f"{100 * s / whole if whole else 0:.1f} | "
                         f"{crossing:.4f} |")
    if len(found["chips"]) > 1:
        lines += ["", "| chip | busy s in the window | collectives s |",
                  "| --- | --- | --- |"]
        for plane, chip in sorted(found["chips"].items()):
            lines.append(f"| `{plane}` | {chip['busy_s']:.4f} | "
                         f"{chip['collective_s']:.4f} |")
    return "\n".join(lines)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(__doc__)
    print(render(read(sys.argv[1])))

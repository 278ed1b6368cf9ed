"""The ``.xplane.pb`` file itself, read with nothing but Python.

``jax.profiler.ProfileData`` shows an event's own stats only, and a
scope's name is not among them: on a v5e trace (looked at by hand, PR
24) the ``jax.named_scope`` path of an operation, as
``jit(_prep)/mv.prep.mask/gather:``, is the stat ``tf_op`` of the
event's *metadata* on the ``XLA Ops`` line. So this module reads the
protobuf wire format of the six messages it needs (xplane.proto: XSpace,
XPlane, XLine, XEvent, XEventMetadata, XStat/XStatMetadata) and nothing
else. Copied from ``tools/trace_spans.py`` (PR 24), which read every
event of every plane; this one decodes only the device planes' two lines
and, of the host's events, the spans the reduction reads.
"""

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SPAN_PREFIXES = ("mv:", "bench:")   # the program's monitors, the harness
SCOPE_STAT = "tf_op"


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


def _map_value(buf):
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


def _scope_path(stats, stat_names) -> str:
    for stat in stats:
        fields = dict(_fields(stat))
        if stat_names.get(fields.get(1)) == SCOPE_STAT:
            # the string itself, or a reference to a stat metadata's name
            return _text(fields[5]) if 5 in fields \
                else stat_names.get(fields.get(7), "")
    return ""


def _events(buf, wanted=None):
    """``(line id, line name, [(metadata id, start_ns, end_ns)])``, only
    the events whose metadata id is in ``wanted`` where that is given.
    Whole nanoseconds, as ``jax.profiler.ProfileData`` gives them: a
    start is the line's timestamp plus the offset's whole nanoseconds, an
    end the start plus the duration's."""
    ident, name, stamp_ns, out = 0, "", 0, []
    for field, value in _fields(buf):
        if field == 1:
            ident = value
        elif field == 2:
            name = _text(value)
        elif field == 3:
            stamp_ns = value
        elif field == 4:
            meta = offset = duration = 0
            for f, v in _fields(value):
                if f == 1:
                    if wanted is not None and v not in wanted:
                        break
                    meta = v
                elif f == 2:
                    offset = v
                elif f == 3:
                    duration = v
            else:
                out.append((meta, offset // 1000, duration // 1000))
    return ident, name, [(m, stamp_ns + a, stamp_ns + a + d)
                         for m, a, d in out]


def _line_name(buf) -> str:
    for field, value in _fields(buf):
        if field == 2:
            return _text(value)
        if field > 2:
            break
    return ""


def _plane(buf):
    name, lines, event_meta, stat_meta = "", [], [], []
    for field, value in _fields(buf):
        if field == 2:
            name = _text(value)
        elif field == 3:
            lines.append(value)
        elif field == 4:
            event_meta.append(value)
        elif field == 5:
            stat_meta.append(value)
    return name, lines, event_meta, stat_meta


def _device(lines, event_meta, stat_meta) -> dict:
    stat_names = {}
    for entry in stat_meta:
        ident, label, _ = _named(_map_value(entry))
        stat_names[ident] = label
    metadata = {}
    for entry in event_meta:
        ident, label, stats = _named(_map_value(entry), stats_field=5)
        metadata[ident] = (label, _scope_path(stats, stat_names))
    found = {MODULE_LINE: [], OP_LINE: []}
    for line in lines:
        if _line_name(line) in found:
            _, line_name, events = _events(line)
            found[line_name] = events
    return {"modules": [(metadata[m][0], a, b)
                        for m, a, b in found[MODULE_LINE]],
            "ops": [(metadata[m][0], a, b, metadata[m][1])
                    for m, a, b in found[OP_LINE]]}


def _host(lines, event_meta) -> list:
    names = {}
    for entry in event_meta:
        ident, label, _ = _named(_map_value(entry))
        if label.startswith(SPAN_PREFIXES):
            names[ident] = label
    spans = []
    if names:
        for line in lines:
            thread, _, events = _events(line, names)
            spans += [(names[m], a, b, thread) for m, a, b in events]
    return spans


def load(path: str) -> dict:
    """``{"devices": {plane: {"modules": [(name, start_ns, end_ns)],
    "ops": [(name, start_ns, end_ns, scope path)]}}, "spans": [(name,
    start_ns, end_ns, thread)]}``: the chips' programs and operations,
    and the ``mv:`` and ``bench:`` spans of every host thread."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices, spans = {}, []
    for field, value in _fields(space):
        if field != 1:
            continue
        name, lines, event_meta, stat_meta = _plane(value)
        if name.startswith(DEVICE_PLANE):
            devices[name] = _device(lines, event_meta, stat_meta)
        elif name == HOST_PLANE:
            spans += _host(lines, event_meta)
    return {"devices": devices, "spans": spans}

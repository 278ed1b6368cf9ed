#!/usr/bin/env python3
"""Cut a recorded trace down to what benchmark/lib/xplane.py reads, so
that it is small enough to keep beside the tests: the device planes'
`XLA Modules` and `XLA Ops` lines and the host's `bench:` spans, without
their stats.   python3 benchmark/tools/trim_trace.py <in.xplane.pb> <out>

Needs tensorflow's xplane_pb2, which the harness itself never imports.
"""

import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

KEEP_LINES = ("XLA Modules", "XLA Ops")


def trim(space):
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != "/host:CPU":
            continue
        kept = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if device and line.name not in KEEP_LINES:
                continue
            events = [e for e in line.events if device or
                      plane.event_metadata[e.metadata_id].name
                      .startswith("bench:")]
            if not events:
                continue
            new = kept.lines.add(id=line.id, name=line.name,
                                 display_name=line.display_name,
                                 timestamp_ns=line.timestamp_ns)
            for e in events:
                new.events.add(metadata_id=e.metadata_id,
                               offset_ps=e.offset_ps,
                               duration_ps=e.duration_ps)
                meta = plane.event_metadata[e.metadata_id]
                kept.event_metadata[e.metadata_id].id = meta.id
                kept.event_metadata[e.metadata_id].name = meta.name
    return out


if __name__ == "__main__":
    space = xplane_pb2.XSpace()
    with open(sys.argv[1], "rb") as f:
        space.ParseFromString(f.read())
    with open(sys.argv[2], "wb") as f:
        f.write(trim(space).SerializeToString())

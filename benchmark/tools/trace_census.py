#!/usr/bin/env python3
"""What a kept trace holds, as one JSON line, to set two traces of one cell
side by side (PR 67: the traced window with and without the profiler's
Python tracer):
    python3 benchmark/tools/trace_census.py <file.xplane.pb | directory>
The device planes with their programs' and operations' counts, the host's
``mv:`` and ``bench:`` spans counted by name, the scopes the reduction found
by program, the idle share and the five largest totals of ``idle_by_span``.
A directory is searched for its one ``.xplane.pb`` (``--keep-trace``'s)."""

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import xplane  # noqa: E402


def census(path: str) -> dict:
    if os.path.isdir(path):
        path, = glob.glob(os.path.join(path, "*.xplane.pb"))
    trace = xplane.load(path)
    reduced = xplane.reduce(trace)
    spans = {}
    for span in trace["spans"]:
        spans[span[0]] = spans.get(span[0], 0) + 1
    return {
        "file_bytes": os.path.getsize(path),
        "device_planes": {plane: {"programs": len(lines["modules"]),
                                  "operations": len(lines["ops"])}
                          for plane, lines in trace["devices"].items()},
        "spans": dict(sorted(spans.items())),
        "scopes": {program: sorted(by)
                   for program, by in sorted(reduced["scopes"].items())},
        "window_s": reduced["window_s"], "busy_s": reduced["busy_s"],
        "idle_share": 1.0 - reduced["busy_s"] / reduced["window_s"],
        "idle_by_span": sorted(reduced["idle_by_span"].items(),
                               key=lambda x: -x[1])[:5]}


if __name__ == "__main__":
    print(json.dumps(census(sys.argv[1])))

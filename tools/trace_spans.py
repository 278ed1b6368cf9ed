#!/usr/bin/env python3
"""Read the program's own spans and scopes out of a profiler trace:

    python3 tools/trace_spans.py <file.xplane.pb>

for a trace kept with ``benchmark/run.py --trace 1 --keep-trace <dir>``
or captured with ``multiverso_tpu.util.trace_to``. The file is read by
``benchmark/lib/xspace.py`` ``load`` and cut by ``benchmark/lib/xplane.py``
``reduce``, the reader and the cuts the benchmark's per-layer metrics go
through and its tests check: this file only arranges what they give in
three tables.

(i) The idle time of the busiest chip by what the host was doing
    (``reduce``'s ``idle_by_span``). Every Dashboard monitor is an
    ``mv:<NAME>`` span on a host thread's line (util/dashboard.py), so
    each idle gap is cut where such spans begin and end, and each piece
    goes to the innermost span open over it on any thread: the shortest
    one, since spans of one thread nest. A span that only waits
    (``xplane.WAITS``) loses to a working span on another thread. A
    piece under no ``mv:`` span goes to the harness's ``bench:`` span
    that covers most of its gap; a trace without ``mv:`` spans so gives
    ``reduce``'s ``gap_totals``.

(ii) Device seconds by named scope within each program (by the stem
    ``xplane.stem`` leaves of its name), summed over the chips, over the
    whole trace and not only the window: an epoch's ``_prep`` runs before
    it. The scopes are the ``jax.named_scope`` names that start with
    ``mv.``; an operation under several is counted under the innermost.
    Beside each total, the part of it that collective operations took on
    the busiest chip (``xplane.is_collective``: all-reduce, all-gather,
    collective-permute and the rest, their ``-start`` and ``-done``
    halves too): what a scope's work costs in crossing chips on a table
    laid over several.

(iii) With more than one chip, each chip's busy and collective seconds in
    the window: which chips a block's programs keep busy (the corpus
    and its `_prep` live on the first chip only, the tables' shards on
    all).

``reduce`` reads scopes and collectives on the busiest chip, inside the
window. A chip's own numbers are ``reduce`` of a trace that holds that
chip alone, and the whole trace is ``reduce`` of one without the
``bench:window`` span.
"""

import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark.lib import xplane  # noqa: E402

NO_SPAN = xplane.NO_SPAN
NO_SCOPE = xplane.NO_SCOPE


def _one_chip(lines, spans) -> dict:
    return xplane.reduce({"devices": {"chip": lines}, "spans": spans})


def _summed(parts) -> dict:
    """``{program stem: {scope: seconds}}`` summed over ``parts``."""
    out = {}
    for part in parts:
        for stem, scopes in part.items():
            slot = out.setdefault(stem, {})
            for scope, seconds in scopes.items():
                slot[scope] = slot.get(scope, 0.0) + seconds
    return out


def report(devices, spans) -> dict:
    reduced = xplane.reduce({"devices": devices, "spans": spans})
    busiest = reduced["busiest"]
    totals = {}
    for name, seconds in reduced["idle_by_span"].items():
        if name != NO_SPAN and not name.startswith(xplane.MV_PREFIX):
            name = xplane.SPAN_PREFIX + name     # as the trace has it
        totals[name] = seconds
    idle = sum(totals.values())
    named = sum(s for name, s in totals.items()
                if name.startswith(xplane.MV_PREFIX))
    window = [s for s in spans if s[0] == xplane.WINDOW_SPAN]
    if not window:      # reduce's own fall-back, over every chip
        times = [t for lines in devices.values() for op in lines["ops"]
                 for t in op[1:3]]
        window = [(xplane.WINDOW_SPAN, min(times), max(times))]
    whole = {plane: _one_chip(lines, []) for plane, lines in devices.items()}
    chips = {busiest: reduced} if len(devices) == 1 else {
        plane: _one_chip(lines, window) for plane, lines in devices.items()}
    return {"gaps": {"window_s": reduced["window_s"], "busiest": busiest,
                     "idle_s": idle,
                     "mv_share": named / idle if idle else 0.0,
                     "totals": totals},
            "scopes": _summed(chip["scopes"] for chip in whole.values()),
            "collectives": whole[busiest]["collective_s_by_scope"],
            "chips": {plane: {"busy_s": chip["busy_s"],
                              "collective_s": chip["collective_s"]}
                      for plane, chip in chips.items()}}


def read(path: str) -> dict:
    trace = xplane.load(path)
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

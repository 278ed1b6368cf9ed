#!/usr/bin/env python3
"""Look at one trace by hand: planes, their lines, and the first events
of each.   python3 benchmark/tools/dump_trace.py <file.xplane.pb> [events]"""

import sys

from jax.profiler import ProfileData


def main(path: str, show: int = 5) -> None:
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:show]:
                print(f"      {e.name!r} start_ns={e.start_ns:.0f} "
                      f"duration_ns={e.duration_ns:.0f}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 5)

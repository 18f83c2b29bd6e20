#!/usr/bin/env python3
"""Look at a raw ``.xplane.pb`` by hand before writing code against it:
planes, lines, and the first events of each line with their stats.

    python3 benchmark/tools/inspect_trace.py <file.xplane.pb> [events]
"""

import sys


def main(path: str, show: int = 4) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:show]:
                stats = {k: v for k, v in list(e.stats)[:8]}
                print(f"    {e.name!r} start_ns={e.start_ns} "
                      f"dur_ns={e.duration_ns} {stats}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 4)

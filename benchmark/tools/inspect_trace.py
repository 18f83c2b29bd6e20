#!/usr/bin/env python3
"""Look at a raw ``.xplane.pb`` by hand before writing code against it:
planes, lines, and the first events of each line with their scope, as
the benchmark's own decoder (benchmark/harness/xplane.py) reads them.

    python3 benchmark/tools/inspect_trace.py <file.xplane.pb> [events]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(path: str, show: int = 4) -> None:
    from benchmark.harness import xplane

    everything = xplane.planes(path, lambda plane: True,
                               lambda plane, line: True,
                               lambda plane, event: True)
    for plane in everything:
        print(f"PLANE {plane['name']!r}: {len(plane['lines'])} lines")
        for line in plane["lines"]:
            print(f"  LINE {line['name']!r}: {len(line['events'])} events")
            for name, start, dur, scope in line["events"][:show]:
                print(f"    {name[:100]!r} start_ns={start} dur_ns={dur} "
                      f"scope={scope!r}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 4)

#!/usr/bin/env python
"""Where tier-1's time goes, from the junit file the tier-1 command writes.

    python scripts/tier1_times.py /tmp/_t1.xml

Prints seconds and cases by test file, the 20 longest cases, the share of
the time held by cases of 20 s and over, and a sixth of the cases' sum (the
least six workers could take) beside the run's own wall.
"""

import sys
import xml.etree.ElementTree as ET
from collections import defaultdict

WORKERS = 6  # the tier-1 command's `-n 6`
LONG = 20.0


def report(xml_text: str) -> str:
    suite = ET.fromstring(xml_text)
    if suite.tag == "testsuites":
        suite = suite[0]
    # A class's cases carry `tests.test_x.TestY`: keep the module.
    cases = [
        (float(c.get("time", 0)),
         ".".join(c.get("classname", "").split(".")[:2]),
         c.get("name", ""))
        for c in suite.iter("testcase")
    ]
    by_file = defaultdict(lambda: [0.0, 0])
    for secs, module, _ in cases:
        by_file[module][0] += secs
        by_file[module][1] += 1
    total = sum(secs for secs, _, _ in cases)
    long_cases = [secs for secs, _, _ in cases if secs >= LONG]
    wall = float(suite.get("time", 0))
    lines = [f"{'s':>8} {'cases':>6}  file"]
    for module, (secs, n) in sorted(by_file.items(), key=lambda kv: -kv[1][0]):
        lines.append(f"{secs:8.1f} {n:6d}  {module}")
    lines.append("")
    lines.append("the 20 longest cases:")
    for secs, module, name in sorted(cases, reverse=True)[:20]:
        lines.append(f"{secs:8.1f}  {module}::{name}")
    lines.append("")
    share = 100 * sum(long_cases) / total if total else 0.0
    lines.append(
        f"{len(cases)} cases, {total:.1f} s in all; {len(long_cases)} of "
        f"{LONG:.0f} s and over hold {sum(long_cases):.1f} s ({share:.1f} %)"
    )
    lines.append(
        f"sum / {WORKERS} = {total / WORKERS:.1f} s; the run's wall {wall:.1f} s "
        f"({wall - total / WORKERS:+.1f} s)"
    )
    return "\n".join(lines)


if __name__ == "__main__":
    with open(sys.argv[1], encoding="utf-8") as f:
        print(report(f.read()))

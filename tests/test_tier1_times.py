"""``scripts/tier1_times.py`` on a junit file of three cases."""

import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

JUNIT = """<?xml version="1.0" encoding="utf-8"?>
<testsuites name="pytest tests"><testsuite name="pytest" errors="0"
 failures="0" skipped="0" tests="3" time="20.5">
<testcase classname="tests.test_a" name="test_long[x-1]" time="30.0" />
<testcase classname="tests.test_a.TestThing" name="test_short" time="6.0" />
<testcase classname="tests.test_b" name="test_other" time="24.0" />
</testsuite></testsuites>"""


def test_report_sums_by_file_and_sets_the_sum_beside_the_wall():
    spec = importlib.util.spec_from_file_location(
        "tier1_times", os.path.join(ROOT, "scripts", "tier1_times.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    lines = module.report(JUNIT).splitlines()
    # by file, longest first, a class's cases under their module
    assert lines[1].split() == ["36.0", "2", "tests.test_a"]
    assert lines[2].split() == ["24.0", "1", "tests.test_b"]
    longest = lines[lines.index("the 20 longest cases:") + 1:][:3]
    assert [line.split()[1] for line in longest] == [
        "tests.test_a::test_long[x-1]", "tests.test_b::test_other",
        "tests.test_a::test_short"]
    assert lines[-2] == ("3 cases, 60.0 s in all; 2 of 20 s and over hold "
                         "54.0 s (90.0 %)")
    assert lines[-1] == "sum / 6 = 10.0 s; the run's wall 20.5 s (+10.5 s)"

"""The set-up's self time: the seconds between the process's start and
the window's first dispatch that no record of the set-up log covers
(``process``: the interpreter's start and the imports up to the
program's; ``init``: ``hvd.init()``; ``trace``, ``lower``, ``backend`` of
every program).  What is left is the later imports, module construction,
the backend's start, transfers, the init programs' and the warm-up's
execution.  The window's first stamp is one step after the first
dispatch, so one median gap is taken off.  None without a ``process``
record (off Linux, or a program whose log has none)."""

from benchmark.harness import registry
from benchmark.harness import trace as tr
from benchmark.harness.stats import median


def covered(records):
    """Seconds in the union of the records' intervals (nested or
    overlapping records count once); None where a record has no
    ``t_start``."""
    if any("t_start" not in r for r in records):
        return None
    return tr.total(tr.union((r["t_start"], r["t_end"]) for r in records))


def read(run):
    log = registry.sibling_metric(__file__, "compile_trace_lower_s")
    records = log.records_before_window(run) or ()
    started = [r["t_start"] for r in records if r["phase"] == "process"]
    inside = covered(records)
    if not started or inside is None:
        return None
    stamps = run["stamps"]
    gap = median([b - a for a, b in zip(stamps, stamps[1:])] or [0.0])
    return stamps[0] - gap - started[0] - inside

"""Programs the persistent compilation cache did not have before the
measured window: the ``miss`` verdicts in the program's compile log
(``compile_trace_lower_s`` says which records count).  0 on a warm run
is the healthy reading; the first run in a checkout misses every
program."""

from benchmark.harness import registry


def read(run):
    log = registry.sibling_metric(__file__, "compile_trace_lower_s")
    records = log.records_before_window(run)
    if records is None:
        return None
    return sum(1 for r in records if r.get("cache") == "miss")

"""95th percentile of the program's own per-request ``queue_wait`` spans
(arrival at the ingest pump to the step that scheduled the request)."""

from benchmark.harness.stats import percentile


def read(run):
    durs = [s["dur"] * 1e3 for s in run.get("spans") or ()
            if s["name"] == "queue_wait"]
    return percentile(durs, 95.0) if durs else None

"""Time to first token, client clock: from the moment a request was
*due* to the first poll that showed a token; 95th percentile over every
request due in the window.  A request that never showed a token counts
as having waited until the drain deadline."""

from benchmark.harness.stats import percentile


def read(run):
    rows = run.get("requests")
    if not rows:
        return None
    deadline = run["window_s"] + run["params"]["drain_limit_s"]
    waits = [((r["first_s"] if r["first_s"] is not None else deadline)
              - r["due_s"]) * 1e3 for r in rows]
    return percentile(waits, 95.0)

"""Time per output token, client clock: per request, (last token seen -
first token seen) / (tokens - 1); 95th percentile over every request due
in the window.  A request that did not finish counts as the worst one
that did."""

from benchmark.harness.stats import percentile


def read(run):
    rows = run.get("requests")
    if not rows:
        return None
    good = [(r["last_s"] - r["first_s"]) / (r["tokens"] - 1) * 1e3
            for r in rows if r["done"] and r["tokens"] > 1]
    if not good:
        return None
    failed = sum(1 for r in rows if not r["done"])
    return percentile(good + [max(good)] * failed, 95.0)

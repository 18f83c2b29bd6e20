"""How late the benchmark's own generator ran: actual submit against the
due time, 95th percentile.  A starved generator must not be read as a
fast server."""

from benchmark.harness.stats import percentile


def read(run):
    late = [(r["sent_s"] - r["due_s"]) * 1e3
            for r in run.get("requests") or () if r["sent_s"] is not None]
    return percentile(late, 95.0) if late else None

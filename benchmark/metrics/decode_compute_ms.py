"""Median of the program's own ``decode_compute`` spans (obs/trace.py,
armed through ``HVDTPU_TRACE`` in the rank's environment) that began in
the window: the host's wall time around one ``engine.step``."""

from benchmark.harness.stats import median


def read(run):
    durs = [s["dur"] * 1e3 for s in run.get("spans") or ()
            if s["name"] == "decode_compute"]
    return median(durs) if durs else None

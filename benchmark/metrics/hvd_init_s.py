"""Seconds inside ``hvd.init()`` before the measured window: device
discovery, ``jax.distributed`` where the call starts it, the topology,
arming the hooks.  The set-up log's ``init`` record; None on a program
that leaves none."""

from benchmark.harness import registry


def read(run):
    log = registry.sibling_metric(__file__, "compile_trace_lower_s")
    inits = [r["seconds"] for r in log.records_before_window(run) or ()
             if r["phase"] == "init"]
    return sum(inits) if inits else None

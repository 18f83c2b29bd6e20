"""Seconds the persistent compilation cache's loads took before the
measured window, over every program: JAX's
``/jax/compilation_cache/cache_retrieval_time_sec``, which the set-up
log keeps on the ``backend`` record of the program that hit.  0 on a run
whose every program missed; None on a program whose log lacks the key."""

from benchmark.harness import registry


def read(run):
    log = registry.sibling_metric(__file__, "compile_trace_lower_s")
    loads = [r["cache_load_s"] for r in log.records_before_window(run) or ()
             if "cache_load_s" in r]
    return sum(loads) if loads else None

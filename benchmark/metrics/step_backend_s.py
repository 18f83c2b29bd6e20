"""Seconds of the step program's ``backend`` phase before the measured
window: with a hit in the persistent cache the key, the entry's load and
its deserialisation (``cache_load_s`` is the load's share), with a miss
XLA's compile.  ``step_trace_s`` says which program is the step."""

from benchmark.harness import registry


def read(run):
    step = registry.sibling_metric(__file__, "step_trace_s")
    return step.step_seconds(run, "backend")

"""Seconds before the measured window in which JAX traced, lowered or
compiled (or loaded) a program other than the step: the state's init,
the broadcasts, the warm-up's helpers.  It is what makes
``compile_trace_lower_s`` read over ``compile_s``.  The union of those
records' intervals (``setup_uncovered_s`` counts the same way);
``step_trace_s`` says which program is the step.  None on a program
whose records are not intervals."""

from benchmark.harness import registry


def read(run):
    found = registry.sibling_metric(__file__, "step_trace_s").split(run)
    if found is None:
        return None
    return registry.sibling_metric(__file__, "setup_uncovered_s").covered(
        found[1])

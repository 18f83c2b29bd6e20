"""Seconds JAX spent lowering the cell's step program to an MLIR module
before the measured window (Mosaic lowers each Pallas call inside it);
``step_trace_s`` says which program is the step."""

from benchmark.harness import registry


def read(run):
    step = registry.sibling_metric(__file__, "step_trace_s")
    return step.step_seconds(run, "lower")

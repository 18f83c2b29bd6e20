"""Summed device time of the all-reduce operations per step on one
device, from the traced window; median over the cell's devices."""

from benchmark.harness import trace as tr
from benchmark.harness.stats import median


def per_device(run, which):
    traced = run.get("trace")
    if not traced or not traced["ops"]:
        return None
    values = [tr.exposed(ops, tr.COLLECTIVE)[which] / traced["steps"] / 1e6
              for ops in traced["ops"].values()]
    return median(values)


def read(run):
    return per_device(run, 0)

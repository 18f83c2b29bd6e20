"""Device time of the Pallas flash-attention kernels per step, forward
and backward, from the traced window: summed over the kernel's events on
one device, over the traced steps; median over the cell's devices."""

from benchmark.harness import trace as tr
from benchmark.harness.stats import median

# The kernels carry no name of their own in the program: XLA names each
# after the module that calls it (``block7.3``).  They are the step's
# only Pallas kernels, three a layer (forward, dk/dv, dq), so the mark
# `load_xplane` gives a Pallas custom call finds exactly them.
KERNEL = tr.PALLAS


def read(run):
    traced = run.get("trace")
    if not traced or not traced["ops"]:
        return None
    per_device = [
        sum(e[2] for e in tr.matching(ops, KERNEL)) / traced["steps"] / 1e6
        for ops in traced["ops"].values()]
    value = median(per_device)
    return value if value > 0 else None

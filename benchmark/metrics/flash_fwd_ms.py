"""Device time of the flash-attention FORWARD kernel per step, from the
traced window: the events the program's own kernel name marks
(``pallas_call(..., name="flash_fwd")`` in ops/flash_attention.py, which
XLA numbers ``flash_fwd.2``), summed on one device over the traced
steps; median over the cell's devices.  A program without the name (the
kernels were ``block<i>.<k>`` before) has nothing to read: None."""

import re

from benchmark.harness import trace as tr
from benchmark.harness.stats import median

KERNEL = re.compile(r"^tpu_custom_call:flash_fwd(\.\d+)?$")


def kernel_ms(run, pattern):
    traced = run.get("trace")
    if not traced or not traced["ops"]:
        return None
    per_device = [
        sum(e[2] for e in tr.matching(ops, pattern)) / traced["steps"] / 1e6
        for ops in traced["ops"].values()]
    value = median(per_device)
    return value if value > 0 else None


def read(run):
    return kernel_ms(run, KERNEL)

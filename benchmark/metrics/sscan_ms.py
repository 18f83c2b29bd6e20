"""Device time per step of the Mamba-1 selective-scan kernels, forward
and backward: the events the program's own kernel names mark
(``pallas_call(..., name="sscan_fwd" | "sscan_bwd")`` in
``horovod_tpu/ops/selective_scan.py``), summed on one device over the
traced steps; median over the cell's devices.  A program without the
kernels has nothing to read: None."""

import re

from benchmark.harness import registry

KERNEL = re.compile(r"^tpu_custom_call:sscan_(fwd|bwd)(\.\d+)?$")


def read(run):
    return registry.sibling_metric(__file__, "flash_fwd_ms").kernel_ms(
        run, KERNEL)

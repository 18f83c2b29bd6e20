"""Device time per step of the flash kernels of the differential
attention layers, window, full and cross alike, forward and backward:
the events the kernels' own names mark (``tpu_custom_call:flash_fwd``,
``flash_bwd_dkdv``, ``flash_bwd_dq``), in a program whose
attention is differential (every attention layer's then is), summed on
one device over the traced steps; median over the cell's devices.
Another program: None."""

import re

from benchmark.harness import registry

KERNEL = re.compile(r"^tpu_custom_call:flash_(fwd|bwd_dkdv|bwd_dq)(\.\d+)?$")


def read(run):
    if "first_layer_index" not in run["ran"]:
        return None
    return registry.sibling_metric(__file__, "flash_fwd_ms").kernel_ms(
        run, KERNEL)

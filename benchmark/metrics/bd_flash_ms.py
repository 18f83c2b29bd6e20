"""Device time per step of the flash kernels under the block-diffusion
mask, forward and backward: the events the kernels' own names mark
(``tpu_custom_call:flash_fwd``, ``flash_bwd_dkdv``, ``flash_bwd_dq``)
among those traced under the scope ``attn_block_diffusion``
(``horovod_tpu/models/transformer.py:_attend`` puts it around the
attention call of a configuration whose mask is the block-diffusion
one), summed on one device over the traced steps; median over the
cell's devices.  A program without the scope: None."""

import re

from benchmark.harness import trace as tr
from benchmark.harness.stats import median

SCOPE = "attn_block_diffusion"
KERNEL = re.compile(r"^tpu_custom_call:flash_(fwd|bwd_dkdv|bwd_dq)(\.\d+)?$")


def read(run):
    traced = run.get("trace")
    if not traced or not traced["ops"]:
        return None
    value = median([
        sum(e[2] for e in tr.matching(tr.under(ops, SCOPE), KERNEL))
        / traced["steps"] / 1e6 for ops in traced["ops"].values()])
    return value if value > 0 else None

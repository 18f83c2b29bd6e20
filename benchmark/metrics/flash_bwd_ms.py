"""Device time of the two flash-attention BACKWARD kernels per step
(``flash_bwd_dkdv`` + ``flash_bwd_dq``, the names the program gives its
``pallas_call`` sites), read as ``flash_fwd_ms`` reads the forward one.
With it, ``flash_fwd_ms`` + ``flash_bwd_ms`` = ``flash_ms``."""

import re

from benchmark.harness import registry

KERNELS = re.compile(r"^tpu_custom_call:flash_bwd_(dkdv|dq)(\.\d+)?$")


def read(run):
    forward = registry.sibling_metric(__file__, "flash_fwd_ms")
    return forward.kernel_ms(run, KERNELS)

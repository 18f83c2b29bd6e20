"""Device time per step of the state-space scan alone
(``horovod_tpu/ops/ssd.py:ssd_scan``: the chunked products, the decays,
the recurrence over the chunk states, the skip term), forward, backward
and whatever of it is recomputed: the operations traced under the scope
``ssd_scan``, which lies inside ``ssm``.  It reads the scope and no
kernel name, so it keeps its meaning the day the scan is a Pallas kernel
traced under the same scope.  A program without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "ssd_scan"


def read(run):
    return tr.scope_ms(run, SCOPE)

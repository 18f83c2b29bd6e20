"""Device time per step of the optimizer: the operations traced under
the scope ``optimizer_update``, summed as ``harness/trace.py:scope_ms``
sums them.  ``hvd.DistributedOptimizer`` traces the wrapped optimizer's
update under it, and the benchmark's builders the step's
``optax.apply_updates`` (XLA names a fusion after its root, and the
update's fusions are rooted at that add).  ``SCOPE`` also makes the name
one of the ``breakdown``'s ``device_scopes``."""

from benchmark.harness import trace as tr

SCOPE = "optimizer_update"


def read(run):
    return tr.scope_ms(run, SCOPE)

"""Device time per step of the blocks' MLP halves
(``models/transformer.py:block_math``: the second norm, both matmuls,
the activation): the operations traced under the scope ``mlp``, forward
and backward (``transpose(...)``) alike, summed as
``harness/trace.py:scope_ms`` sums them.  ``SCOPE`` also makes the name
one of the ``breakdown``'s ``device_scopes``."""

from benchmark.harness import trace as tr

SCOPE = "mlp"


def read(run):
    return tr.scope_ms(run, SCOPE)

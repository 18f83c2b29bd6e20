"""Device time per step of the model's head (the final norm, the
vocabulary-wide matmul and its cast; ResNet's pooled dense layer): the
operations traced under the scope ``head``, forward and backward
(``transpose(...)``) alike, summed as ``harness/trace.py:scope_ms`` sums
them.  ``SCOPE`` also makes the name one of the ``breakdown``'s
``device_scopes``."""

from benchmark.harness import trace as tr

SCOPE = "head"


def read(run):
    return tr.scope_ms(run, SCOPE)

"""Device time per step of the blocks' attention halves
(``models/transformer.py:block_math``: the first norm, the q/k/v and
output projections, the flash kernels between them): the operations
traced under the scope ``attn``, forward and backward (``transpose(...)``)
alike, summed as ``harness/trace.py:scope_ms`` sums them.  ``SCOPE`` also
makes the name one of the ``breakdown``'s ``device_scopes``."""

from benchmark.harness import trace as tr

SCOPE = "attn"


def read(run):
    return tr.scope_ms(run, SCOPE)

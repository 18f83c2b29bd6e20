"""Device time per step of the rows on their way into the expert layers'
grouped matmuls (``horovod_tpu/parallel/moe.py``: ``_forward``'s gather
of the tokens' rows into expert order; ``_backward``'s gather of their
gradients' rows and the product with the weights), which gathers inside
the grouped matmul would end: the operations traced under the scope
``moe_rows_in``, inside ``moe_dispatch``.  A program without the scope:
None."""

from benchmark.harness import trace as tr

SCOPE = "moe_rows_in"


def read(run):
    return tr.scope_ms(run, SCOPE)

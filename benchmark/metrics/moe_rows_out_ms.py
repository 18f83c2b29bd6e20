"""Device time per step of the rows on their way back from the expert
layers' grouped matmuls (``horovod_tpu/parallel/moe.py``: ``_forward``'s
gather by choice into ``[k, n, d]``, its float32 cast and the weighted
sum over a token's choices; ``_backward``'s gradient of the weights and
the gradients' rows gathered back and summed), which one kernel whose
grid follows the routed rows would end: the operations traced under the
scope ``moe_rows_out``, inside ``moe_dispatch``.  A program without the
scope: None."""

from benchmark.harness import trace as tr

SCOPE = "moe_rows_out"


def read(run):
    return tr.scope_ms(run, SCOPE)

"""Device time per step of the scatter that inverts the expert layers'
sort (``horovod_tpu/parallel/moe.py:routing_decision``:
``zeros_like(order).at[order].set(arange)``; integers, so there is no
backward): the operations traced under the scope ``moe_unsort``, inside
``moe_route``.  A program without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "moe_unsort"


def read(run):
    return tr.scope_ms(run, SCOPE)

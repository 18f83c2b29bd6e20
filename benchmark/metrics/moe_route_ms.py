"""Device time per step of the expert layers' routing
(``horovod_tpu/parallel/moe.py:route``: the router matmul in float32, the
sigmoid, top-k, the sort of the chosen rows by expert and its inverse),
forward and backward: the operations traced under the scope
``moe_route``, inside ``mlp``.  A program without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "moe_route"


def read(run):
    return tr.scope_ms(run, SCOPE)

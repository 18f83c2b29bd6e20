"""Device time per step of the expert layers' dispatch
(``horovod_tpu/parallel/moe.py:routed_experts``: every token's row
repeated for its choices and gathered into expert order, the experts'
rows gathered back and summed under their weights), forward and backward:
the operations traced under the scope ``moe_dispatch``, inside ``mlp``.
A program without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "moe_dispatch"


def read(run):
    return tr.scope_ms(run, SCOPE)

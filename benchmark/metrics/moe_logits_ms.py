"""Device time per step of the expert layers' router scores
(``horovod_tpu/parallel/moe.py:route``: the float32 ``jnp.dot`` at
``HIGHEST`` precision over all the router's outputs and, under the
sigmoid rule, the sigmoid), forward and backward: the operations traced
under the scope ``moe_logits``, inside ``moe_route``.  A program without
the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "moe_logits"


def read(run):
    return tr.scope_ms(run, SCOPE)

"""Device time per step of the expert matrices' cast
(``horovod_tpu/parallel/moe.py:apply_routing``: the float32 masters to
the compute dtype, once a pass and outside the branch, and the
gradients' cast back): the operations traced under the scope
``moe_cast``, inside ``moe_experts``.  A program without the scope:
None."""

from benchmark.harness import trace as tr

SCOPE = "moe_cast"


def read(run):
    return tr.scope_ms(run, SCOPE)

"""Device time per step of the expert layers' choice
(``horovod_tpu/parallel/moe.py:route``: ``lax.top_k``, the gather of the
chosen scores, the weights' normalisation or the softmax over the
chosen, the scaling), forward and backward: the operations traced under
the scope ``moe_topk``, inside ``moe_route``.  A program without the
scope: None."""

from benchmark.harness import trace as tr

SCOPE = "moe_topk"


def read(run):
    return tr.scope_ms(run, SCOPE)

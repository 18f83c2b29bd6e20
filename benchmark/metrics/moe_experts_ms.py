"""Device time per step of the routed experts' grouped matmuls
(``horovod_tpu/parallel/moe.py:grouped_ffn``: gate and up as one grouped
matmul, the silu gate, down; the weights' cast to the compute dtype),
forward, backward and whatever of it is recomputed: the operations traced
under the scope ``moe_experts``, which lies inside ``mlp``.  It reads the
scope and no kernel name, so it keeps its meaning whatever computes the
grouped matmul.  A program without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "moe_experts"


def read(run):
    return tr.scope_ms(run, SCOPE)

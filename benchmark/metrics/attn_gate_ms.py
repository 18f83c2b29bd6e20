"""Device time per step of the attention layers' output gate
(``horovod_tpu/models/transformer.py:block_math``: the gate's matmul from
the normed stream, its sigmoid and the product with the attended values,
before the output projection), forward and backward: the operations
traced under the scope ``attn_gate``, inside ``attn``.  A program
without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "attn_gate"


def read(run):
    return tr.scope_ms(run, SCOPE)

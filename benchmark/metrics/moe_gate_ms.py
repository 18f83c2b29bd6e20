"""Device time per step of the experts' gate
(``horovod_tpu/parallel/moe.py``: ``act(gate) * up`` between the two
grouped matmuls in ``_ffn``; in ``_ffn_bwd`` the same again and its
gradient): the operations traced under the scope ``moe_gate``, inside
``moe_experts``.  What is left of ``moe_experts_ms`` beside this, the
cast and the kernels ``gmm`` and ``tgmm`` is the kernels' select and
zeroing.  A program without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "moe_gate"


def read(run):
    return tr.scope_ms(run, SCOPE)

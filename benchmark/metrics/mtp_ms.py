"""Device time per step of the multi-token-prediction module
(``horovod_tpu/models/transformer.py:MTP``: the next token's embedding,
two norms, ``eh_proj``, one more block, its final norm) and its pass
through the model's head, forward and backward: the operations traced
under the scope ``mtp``.  The module's block traces its own ``attn`` and
``mlp`` inside it, so ``attn_ms``, ``mlp_ms`` and ``head_ms`` count that
time too, while ``device_scopes`` files it under ``mtp`` once.  A program
without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "mtp"


def read(run):
    return tr.scope_ms(run, SCOPE)

"""Device time per step of the blocks' Mamba-2 mixer halves
(``models/transformer.py:mamba_mixer`` under ``block_math``: the first
norm, ``in_proj``, the causal conv, the state-space scan, the gated norm,
``out_proj``): the operations traced under the scope ``ssm``, forward and
backward alike, summed as ``harness/trace.py:scope_ms`` sums them; the
counterpart of ``attn_ms``.  A program without the scope has nothing to
read: None.  ``SCOPE`` also makes the name one of the ``breakdown``'s
``device_scopes``."""

from benchmark.harness import trace as tr

SCOPE = "ssm"


def read(run):
    return tr.scope_ms(run, SCOPE)

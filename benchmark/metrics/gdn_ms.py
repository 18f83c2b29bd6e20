"""Device time per step of the blocks' Gated-DeltaNet halves
(``models/transformer.py:gdn_mixer`` under ``block_math``: the first
norm, the projection to q, k, v and z and the one to b and a, the filter
with silu, the L2 norms, the decay, the chunk rule, the gated norm,
``out_proj``): the operations traced under the scope ``gdn``, forward
and backward alike, summed as ``harness/trace.py:scope_ms`` sums them;
the counterpart of ``attn_ms``, ``ssm_ms`` and ``kda_ms``.  A program
without the scope has nothing to read: None.  ``SCOPE`` also makes the
name one of the ``breakdown``'s ``device_scopes``."""

from benchmark.harness import trace as tr

SCOPE = "gdn"


def read(run):
    return tr.scope_ms(run, SCOPE)

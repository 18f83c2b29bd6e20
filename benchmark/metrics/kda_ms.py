"""Device time per step of the blocks' Kimi-Delta-Attention halves
(``models/transformer.py:kda_mixer`` under ``block_math``: the first
norm, the projections to q, k and v, the two low-rank gates and beta, the
three filters with silu, the L2 norms, the decay, the chunk rule, the
gated norm, ``o_proj``): the operations traced under the scope ``kda``,
forward and backward alike, summed as ``harness/trace.py:scope_ms`` sums
them; the counterpart of ``attn_ms``, ``ssm_ms`` and ``short_conv_ms``.
A program without the scope has nothing to read: None.  ``SCOPE`` also
makes the name one of the ``breakdown``'s ``device_scopes``."""

from benchmark.harness import trace as tr

SCOPE = "kda"


def read(run):
    return tr.scope_ms(run, SCOPE)

"""Device time per step of the blocks' gated-short-convolution halves
(``models/transformer.py:short_conv_mixer`` under ``block_math``: the
first norm, ``in_proj``, the two gates and the three-tap causal filter,
``out_proj``): the operations traced under the scope ``short_conv``,
forward and backward alike, summed as ``harness/trace.py:scope_ms`` sums
them; the counterpart of ``attn_ms`` and ``ssm_ms``.  A program without
the scope has nothing to read: None.  ``SCOPE`` also makes the name one
of the ``breakdown``'s ``device_scopes``."""

from benchmark.harness import trace as tr

SCOPE = "short_conv"


def read(run):
    return tr.scope_ms(run, SCOPE)

"""Device time per step of the attention calls of the layers that read
another layer's keys and values (``models/transformer.py:_attend`` puts
the scope around them, inside ``attn``): the flash kernels and the
stacking of the sub-heads around them, forward and backward: the
operations traced under the scope ``attn_cross``.  A program without the
scope: None."""

from benchmark.harness import trace as tr

SCOPE = "attn_cross"


def read(run):
    return tr.scope_ms(run, SCOPE)

"""Device time per step of differential attention's own arithmetic
(``models/transformer.py:_attend_differential``: lambda, the subtraction
of the second map's values, the sub-norm and the scale; the flash calls
stay outside it), inside ``attn``, forward and backward: the operations
traced under the scope ``attn_diff``.  A program without the scope:
None."""

from benchmark.harness import trace as tr

SCOPE = "attn_diff"


def read(run):
    return tr.scope_ms(run, SCOPE)

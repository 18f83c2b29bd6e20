"""Device time per step of the gated short convolutions' elementwise
chains alone (``B * u``, the filter's taps, ``C *``, and their
gradients): the operations traced under the scope ``short_conv_filter``,
which lies inside ``short_conv``, forward, backward and whatever of it
is recomputed.  XLA's fusions today; it reads the scope and no kernel
name, so it keeps its meaning the day the chain is a Pallas kernel
traced under the same scope.  A program without the scope: None."""

from benchmark.harness import trace as tr


def read(run):
    return tr.scope_ms(run, "short_conv_filter")

"""Device time per step of the float32 elementwise chain between a
Kimi-Delta-Attention layer's projections and its chunk rule (three
causal four-tap filters with silu, the two L2 norms over a head's
channels, softplus and the decay, beta's sigmoid, and their gradients):
the operations traced under the scope ``kda_prep``, which lies inside
``kda``, forward, backward and whatever of it is recomputed.  XLA's
fusions today; it reads the scope and no kernel name.  A program without
the scope: None."""

from benchmark.harness import trace as tr


def read(run):
    return tr.scope_ms(run, "kda_prep")

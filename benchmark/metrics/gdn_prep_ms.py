"""Device time per step of the float32 elementwise chain between a
Gated-DeltaNet layer's projections and its chunk rule (one causal
four-tap filter over q, k and v with silu, the two L2 norms over a key
head's channels, softplus and the decay a head, beta's sigmoid, and
their gradients): the operations traced under the scope ``gdn_prep``,
which lies inside ``gdn``, forward, backward and whatever of it is
recomputed.  XLA's fusions today; it reads the scope and no kernel name.
A program without the scope: None."""

from benchmark.harness import trace as tr


def read(run):
    return tr.scope_ms(run, "gdn_prep")

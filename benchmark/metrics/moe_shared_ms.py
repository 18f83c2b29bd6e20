"""Device time per step of the expert layers' shared expert
(``models/transformer.py:routed``: the dense feed-forward every token
takes beside its routed experts, two matmuls and the activation),
forward, backward and whatever of it is recomputed: the operations
traced under the scope ``moe_shared``, inside ``mlp``.  A program
without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "moe_shared"


def read(run):
    return tr.scope_ms(run, SCOPE)

"""Device time per step of what lays the scalar-decay gated delta rule's
operands out for the channel-decay kernels
(``horovod_tpu/ops/kda.py:gated_delta_rule``: ``g`` broadcast over a
head's channels, ``q`` and ``k`` repeated over the value heads, and the
sums that take their gradients back): the operations traced under the
scope ``gdn_spread``, which lies inside ``gdn_scan``.  ``gdn_scan_ms``
less this is the rule's kernels with their own layout changes, so a
later gain there can be told from one that drops the spreading.  A
program without the scope (a rule that takes ``g`` a head as it is, a
tree of before the layer type): None."""

from benchmark.harness import trace as tr


def read(run):
    return tr.scope_ms(run, "gdn_spread")

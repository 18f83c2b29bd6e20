"""Device time per step of the scalar-decay gated delta rule alone
(``horovod_tpu/ops/kda.py:gated_delta_rule``: whatever computes it, and
the operations that lay its operands out, today the spreading of ``g``
over a head's channels and of ``q`` and ``k`` over the value heads for
the channel-decay kernels, and the sums that take their gradients back):
the operations traced under the scope ``gdn_scan``, which lies inside
``gdn``, forward and ``transpose(...)`` alike.  It reads the scope and
no kernel name, so it keeps its meaning whatever implements the rule.
A program without the scope: None."""

from benchmark.harness import trace as tr


def read(run):
    return tr.scope_ms(run, "gdn_scan")

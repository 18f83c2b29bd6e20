"""Device time per step of the chunked gated delta rule alone
(``horovod_tpu/ops/kda.py``: whatever computes it, kernels or XLA's
matmuls, and the operations that lay ``g``, ``beta`` and the cumulated
decays out for it): the operations traced under the scope ``kda_scan``,
which lies inside ``kda``, forward and ``transpose(...)`` alike.  It
reads the scope and no kernel name, so it keeps its meaning whatever
implements the rule.  A program without the scope: None."""

from benchmark.harness import trace as tr


def read(run):
    return tr.scope_ms(run, "kda_scan")

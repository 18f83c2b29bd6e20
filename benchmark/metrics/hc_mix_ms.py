"""Device time per step of the hyper-connections' mixing: the read-out
(scope ``hc_read``: the streams' weighted sum a half's norm and branch
read) and the write-back (scope ``hc_write``: the streams mixed by the
doubly stochastic map plus the branch's output a stream, the float32 sum
and the store), forward, recomputed and backward.  XLA's fusions today;
it reads the scopes and no kernel name, so it keeps its meaning the day
either is a Pallas kernel traced under the same scope.  A program with
neither scope: None."""

from benchmark.harness import trace as tr


def read(run):
    parts = [tr.scope_ms(run, scope) for scope in ("hc_read", "hc_write")]
    found = [part for part in parts if part is not None]
    return sum(found) if found else None

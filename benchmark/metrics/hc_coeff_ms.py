"""Device time per step of making the hyper-connections' maps
(``horovod_tpu/models/hyper_connections.py:coefficients``: the norm over
all the streams' channels, the float32 projection onto ``n^2 + 2 n``
numbers a token, the sigmoids, the clamped exponential and Sinkhorn's
rounds), forward, recomputed and backward: the operations traced under
the scope ``hc_coeff``, which lies outside the halves' ``attn`` and
``mlp``.  A program without the scope: None."""

from benchmark.harness import trace as tr


def read(run):
    return tr.scope_ms(run, "hc_coeff")

"""Device time per step of the blocks' gated-memory-unit halves
(``models/transformer.py:gmu_mixer`` under ``block_math``: the first
norm, ``in_proj``, silu, the product with the scan memory another layer
handed on, ``out_proj``), forward and backward: the operations traced
under the scope ``gmu``.  A program without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "gmu"


def read(run):
    return tr.scope_ms(run, SCOPE)

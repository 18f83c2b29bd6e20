"""Device time per step of latent attention's projections
(``horovod_tpu/models/transformer.py:mla_mixer``: the two low-rank paths
with their norms, RoPE on the rotary channels, the concatenation with the
rotary key all heads share), forward and backward: the operations traced
under the scope ``mla_proj``, inside ``attn`` and beside the flash
kernels and the output projection.  A program without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "mla_proj"


def read(run):
    return tr.scope_ms(run, SCOPE)

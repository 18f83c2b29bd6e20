"""Device time per step of the Mamba-2 mixers' gate and grouped norm
(``models/transformer.py:mamba_mixer``: ``y * silu(z)`` and the RMS norm
over each group's channels, a float32 elementwise chain between the scan
and ``out_proj``), forward, backward and whatever of it is recomputed:
the operations traced under the scope ``ssm_norm``, inside ``ssm``.  A
program without the scope: None."""

from benchmark.harness import trace as tr

SCOPE = "ssm_norm"


def read(run):
    return tr.scope_ms(run, SCOPE)

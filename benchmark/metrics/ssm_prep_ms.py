"""Device time per step of the Mamba-2 mixers' front chain
(``models/transformer.py:mamba_mixer``: the causal filter, its bias and
silu over ``xBC`` and the split into ``x``, ``B`` and ``C``, between
``in_proj`` and the scan), forward, backward and whatever of it is
recomputed: the operations traced under the scope ``ssm_prep``, inside
``ssm``.  The kernels ``ssm_prep_fwd`` and ``ssm_prep_bwd`` of
``horovod_tpu/ops/ssm_chain.py`` where the shape allows, else XLA's
fusions; it reads the scope and no kernel name.  A program without the
scope: None."""

from benchmark.harness import trace as tr

SCOPE = "ssm_prep"


def read(run):
    return tr.scope_ms(run, SCOPE)

"""Device time per step of the two float32 elementwise chains of the
Mamba-2 mixers together (``models/transformer.py:mamba_mixer``): the
filter, silu and split in front of the scan (scope ``ssm_prep``) and the
gate and grouped norm behind it (scope ``ssm_norm``), forward, backward
and whatever of them is recomputed.  The sum of the two scopes' times,
each as ``ssm_prep_ms`` and ``ssm_norm_ms`` read it; the one reading of
the gate and norm in a cell that ``ssm_norm_ms`` does not list.  A
program without either scope (one of before the scope ``ssm_prep``
reads the gate and norm alone under ``ssm_norm_ms``): None."""

from benchmark.harness import trace as tr


def read(run):
    parts = [tr.scope_ms(run, scope) for scope in ("ssm_prep", "ssm_norm")]
    return None if None in parts else sum(parts)

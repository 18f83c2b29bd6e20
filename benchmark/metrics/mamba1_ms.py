"""Device time per step of the blocks' Mamba-1 mixer halves
(``models/transformer.py:selective_scan_mixer`` under ``block_math``: the
first norm, ``in_proj``, the causal conv, ``x_proj``, ``dt_proj``, the
selective scan, the gate, ``out_proj``): the operations traced under the
scope ``ssm``, forward and backward alike, in a program that has
selective-scan layers; the counterpart of ``ssm_ms``, whose ``SCOPE``
already files the scope in the ``breakdown``, so this reader states
none.  A program whose ``ssm`` scope holds another mixer: None."""

from benchmark.harness import trace as tr


def read(run):
    if "selective_scan" not in run["ran"].get("layer_types", ()):
        return None
    return tr.scope_ms(run, "ssm")

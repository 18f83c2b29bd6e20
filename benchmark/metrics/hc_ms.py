"""Device time per step of the hyper-connections, whole: making the maps
(``hc_coeff_ms``) and mixing the streams with them (``hc_mix_ms``), the
three scopes ``hc_coeff``, ``hc_read`` and ``hc_write`` that lie between
the halves' ``attn`` and ``mlp``.  A program without them: None."""

from benchmark.harness import registry


def read(run):
    parts = [registry.sibling_metric(__file__, name).read(run)
             for name in ("hc_coeff_ms", "hc_mix_ms")]
    found = [part for part in parts if part is not None]
    return sum(found) if found else None

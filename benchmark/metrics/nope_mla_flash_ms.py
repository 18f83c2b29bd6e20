"""Device time per step of the flash kernels of a cell whose latent
attention layers have keys wider than values (``qk_nope_head_dim +
qk_rope_head_dim`` over ``v_head_dim``): ``flash_fwd_ms`` +
``flash_bwd_ms``, the kernels' own names (``tpu_custom_call:flash_fwd``,
``flash_bwd_dkdv``, ``flash_bwd_dq``), for a program whose ``ran`` says
so: ``layer_types`` names ``mla`` and no other attention layer, and the
key's width differs from the value's.  Another program, or a run without
a trace: None."""

from benchmark.harness import registry

OTHER_ATTENTION = ("attention", "sliding_attention", "full_attention",
                   "cross_attention")


def unequal_latent_layers(ran) -> int:
    """The latent layers of a program whose every attention call is a
    latent one with keys and values of two widths; 0 for any other."""
    kinds = list(ran.get("layer_types") or ())
    if any(kind in OTHER_ATTENTION for kind in kinds) or not ran.get(
            "v_head_dim") or (
            ran.get("qk_nope_head_dim", 0) + ran.get("qk_rope_head_dim", 0)
            == ran["v_head_dim"]):
        return 0
    return kinds.count("mla")


def read(run):
    if not unequal_latent_layers(run["ran"]):
        return None
    parts = [registry.sibling_metric(__file__, name).read(run)
             for name in ("flash_fwd_ms", "flash_bwd_ms")]
    return None if None in parts else sum(parts)

"""Device time per step of the flash kernels of a cell whose attention
layers are full causal attention with grouped heads (fewer key/value
heads than query heads, no window, no latent): ``flash_fwd_ms`` +
``flash_bwd_ms``, the kernels' own names (``tpu_custom_call:flash_fwd``,
``flash_bwd_dkdv``, ``flash_bwd_dq``), for a program whose ``ran`` says
so: ``layer_types`` names ``full_attention`` and no windowed or latent
layer, and ``num_key_value_heads`` is under ``num_attention_heads``.
Another program, or a run without a trace: None."""

from benchmark.harness import registry

OTHER_ATTENTION = ("sliding_attention", "mla", "cross_attention")


def grouped_full_layers(ran) -> int:
    """The full-attention layers of a program whose every attention call
    is full causal with grouped heads; 0 for any other program."""
    kinds = list(ran.get("layer_types") or ())
    if any(kind in OTHER_ATTENTION for kind in kinds) or not (
            0 < ran.get("num_key_value_heads", 0)
            < ran.get("num_attention_heads", 0)):
        return 0
    return kinds.count("full_attention")


def read(run):
    if not grouped_full_layers(run["ran"]):
        return None
    parts = [registry.sibling_metric(__file__, name).read(run)
             for name in ("flash_fwd_ms", "flash_bwd_ms")]
    return None if None in parts else sum(parts)

"""Of the attention layers of the step that have a norm over each head
of ``q`` and ``k`` or a rotation by position, the share whose chain
between the fused q/k/v matmul and the attention call (scope
``attn_prep``) runs as the Pallas kernel pair of
``horovod_tpu/ops/attn_prep.py`` and not as XLA's fusions: gauge
``attn_prep.kernel_layers`` over ``attn_prep.layers``, which the program
sets while the step is traced (``models/transformer.py``, from
``attn_prep.plan``: the path is read from what the layer is), read from
the program's own registry in this process, as ``ssm_chain_kernel_share``
reads its gauges.  1.0 where every such layer takes the kernels, 0.0
where none does (heads of half a lane tile); a program without the
gauges (no such layer, a tree of before the kernels): None."""


def read(run):
    try:
        from horovod_tpu.obs.registry import get_registry
    except ImportError:
        return None
    gauges = {m["name"]: m["value"] for m in get_registry().snapshot()
              if m["name"].startswith("attn_prep.")}
    layers = gauges.get("attn_prep.layers")
    if not layers:
        return None
    return gauges.get("attn_prep.kernel_layers", 0.0) / layers

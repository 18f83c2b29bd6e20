"""Of the Gated-DeltaNet layers of the step, the share whose rule runs
as the Pallas kernel pair ``kda_fwd`` / ``kda_bwd`` of
``horovod_tpu/ops/kda.py`` and not as the chunk algebra XLA compiles:
gauge ``gdn.kernel_layers`` over ``gdn.layers``, which the program sets
while the step is traced (``models/transformer.py``, from ``kda.plan``:
the path is read from the shape), read from the program's own registry
in this process, as ``ssm_chain_kernel_share`` reads its gauges.  1.0
where every layer takes the kernels; a program without the gauges (no
such layer, a tree of before the layer type): None."""


def read(run):
    try:
        from horovod_tpu.obs.registry import get_registry
    except ImportError:
        return None
    gauges = {m["name"]: m["value"] for m in get_registry().snapshot()
              if m["name"].startswith("gdn.")}
    layers = gauges.get("gdn.layers")
    if not layers:
        return None
    return gauges.get("gdn.kernel_layers", 0.0) / layers

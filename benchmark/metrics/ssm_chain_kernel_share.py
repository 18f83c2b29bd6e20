"""Of the Mamba-2 layers of the step, the share whose two float32
chains (scopes ``ssm_prep`` and ``ssm_norm``) run as the Pallas kernel
pairs of ``horovod_tpu/ops/ssm_chain.py`` and not as XLA's fusions:
gauge ``ssm_chain.kernel_layers`` over ``ssm_chain.layers``, which the
program sets while the step is traced (``models/transformer.py``, from
``ssm_chain.plan``: the path is read from the shape), read from the
program's own registry in this process, as ``remat_kept_share`` reads
its gauges.  1.0 where every layer takes the kernels; a program without
the gauges (no Mamba-2 layer, a tree of before the kernels): None."""


def read(run):
    try:
        from horovod_tpu.obs.registry import get_registry
    except ImportError:
        return None
    gauges = {m["name"]: m["value"] for m in get_registry().snapshot()
              if m["name"].startswith("ssm_chain.")}
    layers = gauges.get("ssm_chain.layers")
    if not layers:
        return None
    return gauges.get("ssm_chain.kernel_layers", 0.0) / layers

"""MiB one Mamba-2 layer's scan keeps for its backward (``y`` and the
chunk-start states, from shapes: ``horovod_tpu/ops/ssd.py:kept_mib``):
the gauge ``ssd.kept_mib``, set while the step is traced, which the
family's builder leaves under ``ran["ssd"]``.  A program without the
gauge: None."""


def read(run):
    return (run["ran"].get("ssd") or {}).get("kept_mib") or None

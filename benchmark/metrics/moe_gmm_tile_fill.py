"""The smallest ``moe.gmm_tile_fill{layer}`` over the expert layers: of
the multiply-adds that a layer's six grouped matmuls execute under the
tiles they got (``horovod_tpu/parallel/moe.py:gmm_tiles``), the share
that is needed; 1.0 where every tile divides its matrix.  The gauge is
set while the step is traced (``models/transformer.py:routed``) and read
here from the program's own registry, in this process, as
``compile_trace_lower_s`` reads the compile log.  A program without the
gauge: None."""


def layer_gauges(name):
    """``{layer: value}`` of the program's gauge ``name``, or None where
    the program has no registry or no layer set it."""
    try:
        from horovod_tpu.obs.registry import get_registry
    except ImportError:
        return None
    found = {m["tags"]["layer"]: m["value"]
             for m in get_registry().snapshot()
             if m["name"] == name and "layer" in m.get("tags", {})}
    return found or None


def read(run):
    fills = layer_gauges("moe.gmm_tile_fill")
    return min(fills.values()) if fills else None

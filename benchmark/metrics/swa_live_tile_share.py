"""Of the (q, k) tiles the windowed layers' flash call walks, the share
that does work: gauge ``flash.tiles_live{layer_type=sliding_attention}``
over ``flash.tiles_grid`` (``horovod_tpu/ops/flash_attention.py:
tile_counts``, set by ``models/transformer.py:_attend`` while the step is
traced: the kernels' ``needed`` predicate over the whole grid), which the
family's builder leaves under ``ran["flash_tiles"]``.  The grid is whole
whatever the mask, so a dead tile costs its grid step and its DMA:
1.0 would be a grid that walks the band alone.  A program without the
gauges: None."""


def read(run):
    tiles = run["ran"].get("flash_tiles", {}).get("sliding_attention")
    if not tiles or not tiles.get("grid"):
        return None
    return tiles["live"] / tiles["grid"]

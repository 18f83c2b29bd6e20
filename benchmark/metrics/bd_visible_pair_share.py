"""Of the (query, key) pairs inside the tiles the flash kernels compute
under the block-diffusion mask, the share the mask shows: gauge
``bd.visible_pairs{layer_type}`` (the mask's equation, over batch and
heads) over ``bd.live_tile_pairs{layer_type}`` (the call's live tiles
times a tile's pairs), both set by
``horovod_tpu/models/transformer.py:_attend_schedule`` while the step is
traced, which the family's builder leaves under
``ran["block_diffusion"]``; a layer type's pair counted once.  What the
kernels compute and mask away is one less this: 0.89 at 16 384 rows in
blocks of 4 under 512 x 256 tiles.  A program without the gauges:
None."""


def read(run):
    counted = run["ran"].get("block_diffusion") or {}
    computed = sum(v or 0 for v in
                   (counted.get("live_tile_pairs") or {}).values())
    if not computed:
        return None
    return sum(v or 0 for v in counted["visible_pairs"].values()) / computed

"""Of the grid steps the flash kernels walk a training step, over every
attention layer type, the share that is a live (q, k) tile: the sum of
gauge ``flash.tiles_live{layer_type}`` over the sum of
``flash.tiles_grid{layer_type}`` (both set from the call's ``FlashPlan``,
``horovod_tpu/ops/flash_attention.py:flash_plan``, by
``models/transformer.py:_attend_schedule`` while the step is traced: the
tiles the mask keeps, and the steps the kernels' grids walk), which the
family's builder leaves under ``ran["flash_tiles"]``, a layer type's pair
counted once.  ``swa_live_tile_share`` reads the window layers alone; this
one also the full, cross and grouped causal calls, whose upper triangle
is as dead.  1.0 is a grid that walks the live tiles and nothing else; a
grid that walks the whole ``nq x nk`` rectangle reads the mask's share
(0.51 for one causal call at 64 x 128 tiles).  A program whose builder
leaves no ``flash_tiles``: None."""


def read(run):
    tiles = run["ran"].get("flash_tiles") or {}
    grid = sum(kind.get("grid") or 0 for kind in tiles.values())
    if not grid:
        return None
    return sum(kind.get("live") or 0 for kind in tiles.values()) / grid

"""The dropless expert layer's way back from the grouped matmuls to the
tokens (``parallel/moe.py``, scope ``moe_rows_out``) as one Pallas TPU
kernel, ``moe_combine``::

    y[t] = sum over the choices j of token t whose expert is held here
           of  w[t, j] * rows[inverse[t k + j]]

``rows [R, d]`` are the sort's first rows in expert order, ``inverse``
the sort's inverse (slot -> row), ``w`` the float32 routing weights (or
none: the plain sum, what the tokens' gradient needs).  XLA's form of it
(:func:`combine_slots`) gathers one row for EVERY slot, ``n k`` of them,
into ``[k, n, d]`` and sums that in float32; where a chip holds an eighth
of the experts seven of eight gathered rows are the row of zeros.  The
kernel reads the held experts' rows once and writes ``y`` once.

**What makes it a kernel and not a scatter.**  The sort is stable, so
inside one expert's group the rows lie in token order: for a tile of
tokens and one held expert the rows that belong to the tile are one
contiguous range of ``rows``.  XLA makes the ranges' bounds for every
(expert, token tile) from ``inverse`` and the held experts' sizes by a
compare and a cumulative sum (``_placement``: no pass over the slots by
gather or scatter), and the kernel takes them by scalar prefetch, with,
a held expert and token, the row that holds that choice (or -1) and its
weight (``[held, n]``, the tokens on the lanes: a program turns its
block once, in VMEM).

**A program** takes one tile of tokens, all ``d`` wide.  For each held
expert in turn it copies the range from HBM in chunks of ``chunk`` rows
(the next expert's first chunk is under way while this one's is worked
on), places a chunk's rows at their tokens by a 0/1 matrix ``[tile,
chunk]`` against the chunk on the MXU (one 1 a row at most, float32
sums: the rows come through exactly), multiplies by the expert's float32
weight a token and adds into a float32 ``[tile, d]`` scratch, which is
rounded once to the output.  Tokens with no held choice come out zero by
the same store.  The multiply-adds go with ``n held d``, whatever the
tile: the tile and the chunk are chosen so that a range is one chunk
nearly always (:func:`plan`).

A token's choices are summed in the order of the held experts, not of
its choices as :func:`combine_slots` does; both in float32 with float32
weights.  Which shapes the kernel takes is :func:`plan`'s to say; the
caller's other shapes, and every call off the TPU, take
:func:`combine_slots` (``flash_attention._interpret_for_backend``, looked
up at call time).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention

_F32 = jnp.float32
_LANE_TILE = 128
# A chunk starts on a whole tile of rows in HBM (16 of bfloat16).
_ALIGN = 16
# Rows a copy brings: the MXU's contraction takes 128 at the price of any
# fewer.
CHUNK = 128
# The rows a (token tile, expert) range should expect, so that with the
# alignment's slack it is one chunk but for a few: the tile follows
# (``plan``).
_EXPECTED_ROWS = 64
TOKEN_TILES = (1024, 512, 256, 128)
# Lanes of the output worked on at a time inside a program.
_LANES = 512
# What a call states: the float32 scratch and the output's two blocks of
# [tile, d], three chunks, the placement's blocks.
_VMEM_LIMIT = 64 * 2 ** 20


def plan(n: int, d: int, held: int, rows: int, itemsize: int):
    """``(token tile, chunk)`` for the kernel from the shapes alone, or
    ``None`` where :func:`combine_slots` runs: ``d`` must be whole
    128-lane tiles, ``rows`` whole row tiles, at least a chunk and
    numbers a float32 holds, the held experts whole sublane tiles up to
    a lane tile (the placement is turned in VMEM), and a tile of
    ``TOKEN_TILES`` divide ``n`` and fit ``_VMEM_LIMIT``.  Of those the
    largest whose ranges should expect no more than ``_EXPECTED_ROWS``
    rows, else the smallest.  A
    range is expected to hold half of an even spread of ``rows`` over the
    held experts and the tiles: ``rows`` is two even shares of the slots
    where the layer works under its row bound (``moe.row_bound``), and
    every slot in the step whose held experts passed that, where they
    hold more than their share by as much."""
    if d % _LANE_TILE or rows % _ALIGN or not CHUNK <= rows <= 2 ** 24 \
            or held % 8 or held > _LANE_TILE:
        return None
    tiles = [t for t in TOKEN_TILES if n % t == 0
             and vmem_bytes(t, CHUNK, d, held, itemsize) <= _VMEM_LIMIT]
    if not tiles:
        return None
    fit = [t for t in tiles if t * rows <= 2 * _EXPECTED_ROWS * n * held]
    return (fit[0] if fit else tiles[-1]), CHUNK


def vmem_bytes(tile: int, chunk: int, d: int, held: int, itemsize: int) -> int:
    """What :func:`plan` counts against ``_VMEM_LIMIT``: the scratch, the
    output's block twice (float32 at most), the three chunks, the two
    placement blocks twice and once more turned (a lane tile wide), and
    one ``_LANES`` wide product."""
    return (3 * 4 * tile * d + 3 * chunk * d * itemsize
            + 2 * 4 * tile * (2 * held + _LANE_TILE)
            + 2 * 4 * tile * min(d, _LANES))


def slots(rows, inverse, k: int):
    """``rows`` (values of the sort's first rows) by choice and token,
    ``[k, n, ...]``: choice ``j`` of token ``t`` takes
    ``rows[inverse[t k + j]]``, and a row of zeros where its row lies past
    ``rows`` (its expert is held elsewhere).  One gathered row a SLOT:
    the form of the way back that XLA runs, kept for the shapes and
    backends the kernel does not take and as its oracle.  Choice first,
    so that a choice's rows are a ``[n, d]`` array in whole tiles and a
    sum over the choices adds ``k`` of them."""
    past = rows.shape[0]
    if past < inverse.shape[0]:
        rows = jnp.concatenate([rows, jnp.zeros_like(rows[:1])])
    return jnp.take(rows, jnp.minimum(inverse, past).reshape(-1, k).T,
                    axis=0, mode="clip")


def combine_slots(rows, weights, inverse, k: int):
    """The way back by the slots, float32 ``[n, d]``: a token's ``k``
    gathered rows summed in the order of its choices."""
    if weights is None:
        return slots(rows, inverse, k).sum(axis=0, dtype=_F32)
    return jnp.einsum("knd,nk->nd", slots(rows, inverse, k).astype(_F32),
                      weights)


def combine(rows, weights, inverse, held_sizes, *, k: int, dtype,
            interpret: bool):
    """``y [n, d]`` in ``dtype``: the sum over a token's chosen and held
    experts of ``w * rows[row of that slot]``, accumulated in float32.
    ``rows [R, d]`` in expert order; ``weights [n, k]`` float32, or
    ``None`` for the plain sum; ``inverse [n k]`` slot -> row (a row past
    ``R`` or past the held experts' groups adds nothing); ``held_sizes
    [held]`` the held experts' rows, which lie first and within ``R``.
    The kernel on the chip where :func:`plan` gives tiles; under
    ``interpret`` (the package's rule says so off the TPU) and elsewhere
    :func:`combine_slots`."""
    tiles = None if interpret else plan(
        inverse.shape[0] // k, rows.shape[1], held_sizes.shape[0],
        rows.shape[0], rows.dtype.itemsize)
    if tiles is None:
        return combine_slots(rows, weights, inverse, k).astype(dtype)
    return combine_rows(rows, weights, inverse, held_sizes, k=k, tiles=tiles,
                        dtype=dtype)


def engaged(n: int, d: int, held: int, rows: int, dtype) -> bool:
    """Whether a layer of these shapes runs the kernel on this backend
    (gauge ``moe.combine_kernel_layers``)."""
    return not flash_attention._interpret_for_backend(
        jax.default_backend()) and plan(
            n, d, held, rows, jnp.dtype(dtype).itemsize) is not None


def _placement(weights, inverse, held_sizes, k: int, tile: int):
    """Where the kernel finds a token tile's rows, from the sort's
    inverse: ``table [held (tiles + 1)]`` int32, the first row of expert
    ``e`` that belongs to token tile ``i`` at ``e (tiles + 1) + i`` (the
    range ends where the next tile's starts; the group's own end closes
    the last); ``pos [held, n]`` the row that holds token ``t``'s choice
    of expert ``e`` or -1, as float32 (exact under ``2 ** 24`` rows: the
    kernel takes an expert's column by a sum over the lanes, which the
    chip has for float32 alone); ``wtok [held, n]`` that choice's weight
    or 0.  The tokens on the lanes: ``[n, held]`` arrays would lie in
    HBM padded to 128 lanes, 16 MiB each at 32 768 tokens.
    Compares against the groups' bounds and a cumulative sum, the tokens
    on the lanes: nothing is gathered or scattered."""
    n, held = inverse.shape[0] // k, held_sizes.shape[0]
    ends = jnp.cumsum(held_sizes)[:, None, None]
    starts = ends - held_sizes[:, None, None]
    inv = inverse.reshape(n, k).T[None]                    # [1, k, n]
    inside = (inv >= starts) & (inv < ends)                # [held, k, n]
    pos = jnp.max(jnp.where(inside, inv, -1), axis=1)      # [held, n]
    counts = (pos >= 0).reshape(held, n // tile, tile).sum(-1, dtype=jnp.int32)
    table = starts[:, 0] + jnp.concatenate(
        [jnp.zeros((held, 1), jnp.int32), jnp.cumsum(counts, axis=1)], axis=1)
    wtok = None if weights is None else jnp.where(
        inside, weights.astype(_F32).T[None], 0.0).sum(axis=1)
    return table.reshape(-1), pos.astype(_F32), wtok


def _kernel(table_ref, pos_ref, *refs, held: int, tiles: int, chunk: int,
            weighted: bool):
    if weighted:
        w_ref, rows_ref, out_ref, buf, sem, acc, by_token = refs
    else:
        rows_ref, out_ref, buf, sem, acc, by_token = refs
        w_ref = None
    i = pl.program_id(0)
    d = acc.shape[1]
    last = rows_ref.shape[0] - chunk
    exact = (dict(precision=lax.Precision.HIGHEST)
             if rows_ref.dtype == _F32 else {})

    def bounds(e):
        lo = table_ref[e * (tiles + 1) + i]
        hi = table_ref[e * (tiles + 1) + i + 1]
        return lo, hi, lo // _ALIGN * _ALIGN

    def copy(slot, want):
        start = pl.multiple_of(jnp.minimum(want, last), _ALIGN)
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(start, chunk)], buf.at[slot], sem.at[slot])

    def add(slot, want, pos, w):
        # the chunk's rows by number; one that an earlier chunk of the
        # range brought (the last chunk is moved back into ``rows``)
        # matches no token
        cols = jnp.minimum(want, last) + lax.broadcasted_iota(
            jnp.int32, (1, chunk), 1)
        hit = (pos == jnp.where(cols >= want, cols, -2).astype(_F32)
               ).astype(buf.dtype)
        for at in range(0, d, _LANES):
            lanes = slice(at, min(at + _LANES, d))
            placed = jnp.dot(hit, buf[slot, :, lanes],
                             preferred_element_type=_F32, **exact)
            acc[:, lanes] += placed if w is None else placed * w

    def turned(ref, at):
        # the placement's ``[held, tile]`` block with the tokens on the
        # sublanes, where the 0/1 matrix and the output have them: the
        # experts filled up to a lane tile, one turn a program
        block = ref[...]
        fill = jnp.zeros((_LANE_TILE - held, block.shape[1]), _F32)
        by_token[at] = jnp.concatenate([block, fill], axis=0).T

    def column(at, e):
        # expert ``e``'s column of it, for an ``e`` the loop carries: a
        # masked sum over the lanes, since a lane cannot be sliced at a
        # traced index
        block = by_token[at]
        lane = lax.broadcasted_iota(jnp.int32, block.shape, 1) == e
        return jnp.sum(jnp.where(lane, block, 0.0), axis=1, keepdims=True)

    def first_chunk(e):
        lo, hi, first = bounds(e)
        pl.when(hi > lo)(lambda: copy(e % 2, first).start())

    def expert(e, carry):
        lo, hi, first = bounds(e)
        pl.when(e + 1 < held)(
            lambda: first_chunk(jnp.minimum(e + 1, held - 1)))
        pos = column(0, e)
        w = column(1, e) if weighted else None

        def chunks(c, carry):
            # the first is under way since the expert before; a further
            # one (rare: ``plan``) is waited for where it is asked for
            want = first + c * chunk
            slot = jnp.where(c == 0, e % 2, 2)
            pl.when(c > 0)(lambda: copy(slot, want).start())
            copy(slot, want).wait()
            add(slot, want, pos, w)
            return carry

        lax.fori_loop(
            0, jnp.where(hi > lo, -(-(hi - first) // chunk), 0), chunks, 0)
        return carry

    first_chunk(0)      # under way while the scratch is zeroed
    acc[...] = jnp.zeros_like(acc)
    turned(pos_ref, 0)
    if weighted:
        turned(w_ref, 1)
    lax.fori_loop(0, held, expert, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("k", "tiles", "dtype", "interpret"))
def combine_rows(rows, weights, inverse, held_sizes, *, k: int, tiles,
                 dtype, interpret: bool = False):
    """:func:`combine` by the kernel, under ``tiles`` (what :func:`plan`
    gave, or any ``(token tile, chunk)`` the shapes admit; ``interpret``
    runs it through the Pallas interpreter, for the tests)."""
    tile, chunk = tiles
    n, d = inverse.shape[0] // k, rows.shape[1]
    held = held_sizes.shape[0]
    table, pos, wtok = _placement(weights, inverse, held_sizes, k, tile)
    by_token = pl.BlockSpec((held, tile), lambda i, table: (0, i))
    return pl.pallas_call(
        functools.partial(_kernel, held=held, tiles=n // tile, chunk=chunk,
                          weighted=wtok is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tile,),
            in_specs=[by_token] * (1 if wtok is None else 2)
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda i, table: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((3, chunk, d), rows.dtype),
                pltpu.SemaphoreType.DMA((3,)),
                pltpu.VMEM((tile, d), _F32),
                pltpu.VMEM((1 if wtok is None else 2, tile, _LANE_TILE),
                           _F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_combine",
    )(table, pos, *(() if wtok is None else (wtok,)), rows)

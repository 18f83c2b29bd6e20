"""Pallas TPU flash attention.

The single-chip hot kernel under the transformer model family (and the
per-shard block compute of :mod:`horovod_tpu.parallel.ring_attention`).
The reference framework has no kernels of its own — its FLOPs live in
cuDNN via TF/torch; on TPU the idiomatic equivalent is a Pallas kernel
that keeps the (S, S) score matrix out of HBM entirely.

Design (the standard flash recurrence, TPU-shaped; what the chip said of
each choice is in ``docs/performance.md``, "The flash kernels on the
chip"):

* What a call runs is decided once, from its shapes alone, by
  :func:`flash_plan`: the tiles, the table of the live ones and the tile
  counts, how the forward holds K and V, the backward's form and the
  VMEM each states.  ``flash_attention`` makes the :class:`FlashPlan`
  and hands it to the kernels as their static argument; nothing below
  asks again, and a caller that wants to know what its call will do asks
  the same function.
* Forward: grid ``(batch*heads, steps)``, the second axis one head's
  live (Q tile, K tile) pairs, Q tile major; each program owns one Q
  tile and one (block_k, d) K/V tile — the online-softmax state rides
  VMEM scratch across a Q row's consecutive steps.
  Wherever a kv row's K and V fit ``_FUSED_BWD_VMEM_LIMIT`` (every
  benchmark shape, up to 30208 keys at head size 128 in bfloat16) the
  forward holds the row resident, fetched from HBM once for all the
  grid steps that read it, and slices its tile; a longer row streams
  (1, block_k, d) tiles, one a grid step, and peak memory is
  O(block_q*d + block_k*d), independent of S.
* fp32 accumulators regardless of input dtype (bf16 in, bf16 out, fp32
  softmax state — the MXU-native mixed precision).
* Every kernel forms its score tile TRANSPOSED (``k @ q.T``: keys on
  sublanes, queries on lanes), so a per-query statistic (running max
  ``m``, running sum ``l``, the saved logsumexp, the backward's
  ``delta``) is one lane of a ``(1, block_q)`` row: it is reduced over
  sublanes with elementwise work, broadcast back the same way, and
  crosses HBM as ``[Z, S]``, a 2 KB row per Q tile.  Nothing in a tile
  loop goes through the cross-lane unit and no score-sized tile is
  ever transposed.
* The grids walk the live tiles alone.  A causal call's upper triangle
  is never visited, not just masked; with a window, nor are the tiles
  entirely below the band; under the block-diffusion mask (a noised
  copy beside the clean one: ``flash_attention`` says what sees what),
  nor a noised key's tiles outside its own blocks, nor any noised key's
  from a clean row.  ``flash_plan`` lists one head's live pairs
  (``_tile_live``, the one predicate) and every kernel takes them as a
  small int32 table by scalar prefetch (``pltpu.PrefetchScalarGridSpec``:
  the table is in SMEM before the body runs): the index maps and the
  body read the step's ``i`` and ``j`` from it, and its ``edges`` column
  says where an accumulator opens and closes (``_q_major_walk`` for the
  forward and the Q-outermost backward, ``_k_major_walk`` for the
  K-outermost one).  One path, chosen by the mask: a call without a mask
  has the whole rectangle for its table.  A table past
  ``_TILE_TABLE_SMEM_LIMIT`` (no benchmark shape; 131072 keys in two
  passes) is not made: the same kernels then walk the ``nq x nk``
  rectangle by arithmetic on the step and skip a dead tile's body, which
  costs its grid step (0.07-0.35 us, and where tiles stream their DMA)
  and no arithmetic.  Either walk adds the same float32 terms in the
  same order: the results are equal to the bit.
* Backward is a blockwise recompute from the saved logsumexp, wired via
  ``jax.custom_vjp`` so the op drops into training.  ``delta =
  rowsum(do * o)`` is computed once per call, outside the kernels.  ONE
  kernel forms each tile's ``p`` and ``ds`` once and takes dq, dk and dv
  from them, in one of two forms.  ``"dkdv_resident"``: Q tile
  outermost (the forward's table), dq's accumulator and a whole kv
  row's dk and dv
  accumulators resident, wherever that fits ``_FUSED_BWD_VMEM_LIMIT``,
  the 32 MiB the call then states (a head's channels fill whole 128-lane
  tiles, so 8192 keys at head size 64 or 128 and 4096 keys at head size
  256).  ``"dq_resident"``: K tile outermost (for each K tile the
  query heads' Q tiles that see it; a pair's dq is written at its last
  live K tile), the tile's dk and dv
  accumulators and the kv row's dq resident, where that fits instead
  (8192 keys at head size 256, latent attention's shape, up to 26624;
  16384 keys at head size 64).  Where neither fits the 32 MiB,
  whichever of the two counts less, stating its own count, up to
  ``_FUSED_BWD_VMEM_CEILING``, 48 of the chip's 128 MiB (16384 keys at
  head size 128 with seven query heads a key/value head, Q tile
  outermost at 36.25 MiB; up to 22016 keys there, 43008 at head size 256
  with dq resident).  ``"two_passes"`` for a longer row: dk/dv, then dq,
  each recomputing ``p`` and ``ds``, VMEM independent of S.
* The value width is the values' own (``dv = v.shape[-1]``): ``v``,
  ``o``, ``do`` and dv carry it, ``q``, ``k``, dq and dk the head size
  ``d``, and the default scale stays ``d ** -0.5``.  The kernel bodies
  do not know the difference (``k q^T`` contracts ``d``; ``P V``, ``dp =
  v do^T`` and ``dv += p do`` contract or produce ``dv``): what reads it
  is the block shapes, the forward's ``acc`` as ``[dv, bq]``, the
  backward's dv accumulator (``[S, dv]`` with the Q tile outermost,
  ``[bk, dv]`` with the K tile outermost, beside dk's ``[S, d]`` /
  ``[bk, d]``; dq's ``[d, bq]`` rows keep ``d`` in every form) and the
  VMEM counts, the dk and dv halves each at its own padded lanes.  A
  call with ``dv == d`` is the program it was, spec for spec.  The
  caller with two widths is differential attention
  (``models/transformer.py:_attend_differential``): 40 query rows of 64
  on 20 key/value rows with values of 128, each score map formed once.
* Off-TPU (the CPU test mesh) the same kernel runs through the Pallas
  interpreter, so correctness tests don't need TPU hardware.
* The ``pallas_call`` sites are named ``flash_fwd``, ``flash_bwd_dkdv``
  and ``flash_bwd_dq``: XLA calls the compiled instruction after the
  name (``flash_fwd.2``), so a device trace tells the kernels apart and
  a later Pallas kernel is not counted as attention.  The one-kernel
  backward keeps the name ``flash_bwd_dkdv`` and covers dq, dk and dv
  under it (the benchmark's reader knows the two backward names and no
  third); ``flash_bwd_dq`` occurs only where the two passes ran, so a
  trace says which path every call took.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import scopes

NEG_INF = float(jnp.finfo(jnp.float32).min) / 2
# dot_general numbers for a.T @ b: contract the rows (the keys) of both.
_CONTRACT_ROWS = (((0,), (0,)), ((), ()))


def _pick_block(seq: int, want: int) -> int:
    """Largest power-of-two block <= want that divides seq."""
    b = min(want, seq)
    while seq % b:
        b //= 2
    return max(b, 1)


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    """What one flash call runs, read from its shapes alone
    (:func:`flash_plan`); the kernels' static argument."""

    heads: int
    kv_heads: int
    block_q: int                # the tiles as ``_pick_block`` made them
    block_k: int
    causal: bool
    window: Optional[int]       # None where it reaches every earlier key
    # Which mask the call runs: "full" (none), "causal", "window" (the
    # triangle under a band) or "block_diffusion", whose ``block``
    # is the block length ``B`` (``None`` under the other three).
    mask: str
    block: Optional[int]
    # Over all batch x head rows: the (q, k) tiles ``_tile_live`` admits
    # under the call's mask, the steps the kernels' grids walk, and the
    # whole ``nq x nk`` rectangle.  The grid walks the live tiles alone
    # (``tiles_grid == tiles_live``) wherever their table fits
    # ``_TILE_TABLE_SMEM_LIMIT``; past it the rectangle, where a dead
    # tile costs its grid step and no arithmetic.
    tiles_live: int
    tiles_grid: int
    tiles_mask: int
    # One head's live (Q tile, K tile) pairs, Q tile major: the table the
    # grids walk, the kernels' scalar-prefetch operand (``_q_major_walk``;
    # ``_k_major_walk`` turns it for the K-outermost kernel).  ``None``
    # where the grid stays the rectangle.
    live_tiles: Optional[tuple] = dataclasses.field(repr=False)
    # The forward: a kv row's K and V whole in VMEM (fetched once a row,
    # under GQA once for the group's query heads), or (1, block_k, .)
    # tiles streamed, one a grid step, VMEM independent of S; and the
    # VMEM its call states (0: nothing, the compiler's default).
    fwd_kv_resident: bool
    fwd_vmem_bytes: int
    # The backward: "dkdv_resident" (one kernel, Q tile outermost, a kv
    # row's dk and dv accumulators resident), "dq_resident" (one kernel,
    # K tile outermost, the group's dq rows resident) or "two_passes";
    # and the VMEM its one kernel states (0: the two passes state none).
    bwd_form: str
    bwd_vmem_bytes: int

    @property
    def bwd_kernels(self) -> int:
        return 2 if self.bwd_form == "two_passes" else 1


def flash_plan(q, k, v, *, causal: bool = False, block_q: int = 512,
               block_k: int = 256, window: Optional[int] = None,
               block_diffusion: Optional[int] = None) -> FlashPlan:
    """The :class:`FlashPlan` of ``flash_attention(q, k, v, ...)`` with
    the same keyword arguments, from the shapes and the dtype of ``q``,
    ``k`` and ``v`` alone (arrays or ``jax.ShapeDtypeStruct``); raises
    what that call would raise of them.  Plain Python, for the call
    itself and for whoever counts while a step is traced.

    The plan's ``mask`` says which of four the call runs: none,
    ``causal``, ``causal`` with a ``window``, or ``block_diffusion=B``,
    under which the tiles are cut from half the sequence so that none
    straddles the noised and the clean copy.  Tiles, table and VMEM follow from the
    shapes in the same way under each.

    The forward holds a kv row resident wherever
    ``_fwd_resident_vmem_bytes`` fits ``_FUSED_BWD_VMEM_LIMIT`` and
    states a limit only where the count passes the compiler's default
    scoped limit, then the count rounded up to a MiB.  The backward is
    ``"dkdv_resident"`` wherever ``_fused_bwd_vmem_bytes`` fits
    ``_FUSED_BWD_VMEM_LIMIT``, ``"dq_resident"`` where
    ``_dq_resident_bwd_vmem_bytes`` fits it instead, both then stating
    the limit; above both, whichever of the two counts less, if that
    fits ``_FUSED_BWD_VMEM_CEILING``, stating its own count rounded up
    to a MiB; ``"two_passes"`` above that."""
    b, s, h, d = q.shape
    if k.shape[:3] != v.shape[:3]:
        raise ValueError(
            f"flash_attention requires k and v matching in batch, sequence "
            f"and head count (the value width is v's own), got "
            f"{k.shape}/{v.shape}"
        )
    hkv, dv = k.shape[2], v.shape[3]
    if k.shape[0] != b or k.shape[1] != s or k.shape[3] != d or h % hkv:
        raise ValueError(
            f"flash_attention q {q.shape} incompatible with k/v {k.shape}: "
            "batch/seq/head_dim must match and num_heads must be a "
            "multiple of num_kv_heads (MQA/GQA)"
        )
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if window >= s:
            window = None  # full causal; skip/mask logic not needed
    diffusion = None
    if block_diffusion is not None:
        if causal or window is not None:
            raise ValueError(
                "block_diffusion is a mask of its own: it takes neither "
                "causal=True nor a window")
        if (block_diffusion < 1 or block_diffusion & (block_diffusion - 1)
                or s % 2 or (s // 2) % block_diffusion):
            raise ValueError(
                f"block_diffusion={block_diffusion} must be a power of two "
                f"that divides half the sequence (the noised copy, then "
                f"the clean one), got {s} rows")
        diffusion = (s // 2, block_diffusion)
    mask = ("block_diffusion" if diffusion else "window"
            if window is not None else "causal" if causal else "full")
    # under the block-diffusion mask no tile straddles the two copies
    tiled = s // 2 if diffusion else s
    bq, bk = _pick_block(tiled, block_q), _pick_block(tiled, block_k)
    nq, nk = s // bq, s // bk
    live = tuple((i, j) for i in range(nq) for j in range(nk)
                 if _tile_live(i, j, bq, bk, causal, window, diffusion))
    itemsize = jnp.dtype(q.dtype).itemsize

    fwd = _fwd_resident_vmem_bytes(s, d, dv, bq, bk, itemsize)
    resident = fwd <= _FUSED_BWD_VMEM_LIMIT
    stated = resident and fwd > _DEFAULT_SCOPED_VMEM

    q_outer = _fused_bwd_vmem_bytes(s, d, bq, bk, itemsize, dv)
    k_outer = _dq_resident_bwd_vmem_bytes(s, d, bq, bk, itemsize, h // hkv,
                                          dv)
    if q_outer <= _FUSED_BWD_VMEM_LIMIT:
        form, bwd_vmem = "dkdv_resident", _FUSED_BWD_VMEM_LIMIT
    elif k_outer <= _FUSED_BWD_VMEM_LIMIT:
        form, bwd_vmem = "dq_resident", _FUSED_BWD_VMEM_LIMIT
    else:
        count, form = min((q_outer, "dkdv_resident"),
                          (k_outer, "dq_resident"))
        bwd_vmem = _whole_mib(count)
        if count > _FUSED_BWD_VMEM_CEILING:
            form, bwd_vmem = "two_passes", 0
    # the call's largest table: the forward's, or the K-outermost
    # backward's, a row a query head of the group
    columns = (_Q_MAJOR_COLUMNS if form == "dkdv_resident"
               else _K_MAJOR_COLUMNS * (h // hkv))
    walks_table = 4 * columns * len(live) <= _TILE_TABLE_SMEM_LIMIT
    return FlashPlan(
        heads=h, kv_heads=hkv, block_q=bq, block_k=bk, causal=causal,
        window=window, mask=mask, block=block_diffusion,
        tiles_live=b * h * len(live),
        tiles_grid=b * h * (len(live) if walks_table else nq * nk),
        tiles_mask=b * h * nq * nk,
        live_tiles=live if walks_table else None,
        fwd_kv_resident=resident,
        fwd_vmem_bytes=_whole_mib(fwd) if stated else 0,
        bwd_form=form, bwd_vmem_bytes=bwd_vmem)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 256,
    window: Optional[int] = None,
    block_diffusion: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention over ``q`` ``[batch, seq, heads, head_dim]``, ``k``
    ``[batch, seq, kv heads, head_dim]`` and ``v`` ``[batch, seq, kv heads,
    value_dim]``; returns ``[batch, seq, heads, value_dim]``.  The value
    width is the values' own: it need not be the keys' (differential
    attention reads values twice as wide as its keys), and the default
    scale stays ``head_dim ** -0.5``.  What has to match: batch and
    sequence of all three, the key/value head count of ``k`` and ``v``,
    the head size of ``q`` and ``k``, and ``heads`` a multiple of ``kv
    heads`` (MQA/GQA); anything else raises.

    Differentiable; numerically matches
    :func:`horovod_tpu.parallel.local_attention` to fp32 tolerance.
    ``interpret=None`` compiles the kernel on backend ``tpu`` and runs
    the Pallas interpreter on backend ``cpu`` (the test mode); any other
    backend raises.

    ``window=W`` (requires ``causal=True``) restricts each position to
    its last ``W`` keys (self included) — Mistral-style sliding-window
    attention.  Tiles entirely outside the band are SKIPPED in forward
    and backward (the same mechanism as the causal upper-triangle skip),
    so compute scales with ``S*W``, not ``S^2``; ``W >= S`` degenerates
    to plain causal.

    ``block_diffusion=B`` (a power of two; neither ``causal`` nor a
    window beside it) is the training mask of a block-diffusion model
    (arXiv:2503.09573): the ``S = 2L`` rows are a noised copy of ``L``
    tokens, then the clean one, each in blocks of ``B``.  A noised row
    sees the noised rows of its own block and the clean rows of every
    EARLIER block; a clean row sees the clean rows of its own block and
    of every earlier one; no clean row sees a noised key.  Every row sees
    its own block, so no softmax is empty.  The tiles are cut from ``L``
    (none straddles the two copies) and the dead ones are skipped like
    the causal call's: at 16384 rows of 512 x 256 tiles, 576 of a head's
    2048 are live.
    """
    plan = flash_plan(q, k, v, causal=causal, block_q=block_q,
                      block_k=block_k, window=window,
                      block_diffusion=block_diffusion)
    b, s, h, d = q.shape
    scale_ = scale if scale is not None else d ** -0.5
    if interpret is None:
        interpret = _interpret_for_backend(jax.default_backend())
    # [B,S,H,D] -> [B*H, S, D]: one grid row per (batch, head).  GQA/MQA:
    # k/v fold to [B*HKV, S, D] and the kernels' index maps route each q
    # head to its kv group — no broadcast materialization.
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(
        b * x.shape[2], s, x.shape[3]
    )
    out = _flash(fold(q), fold(k), fold(v), plan, scale_, bool(interpret))
    return out.reshape(b, h, s, v.shape[3]).transpose(0, 2, 1, 3)


def _interpret_for_backend(backend: str) -> bool:
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"flash_attention: backend {backend!r} is neither 'tpu' (compiled "
        "kernel) nor 'cpu' (Pallas interpreter, the test mode); pass "
        "interpret= explicitly to run it anywhere else"
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, plan, scale, interpret):
    o, _ = _flash_fwd_kernel(q, k, v, plan, scale, interpret)
    return o


def _flash_fwd(q, k, v, plan, scale, interpret):
    o, lse = _flash_fwd_kernel(q, k, v, plan, scale, interpret)
    # named here, not at the call site: the residual has to be the named
    # value, or a rematerialised block reruns the kernel to get it
    o = checkpoint_name(o, scopes.FLASH_OUT)
    lse = checkpoint_name(lse, scopes.FLASH_LSE)
    return o, (q, k, v, o, lse)


def _flash_bwd(plan, scale, interpret, res, do):
    q, k, v, o, lse = res
    return _flash_bwd_pallas(q, k, v, o, lse, do, plan, scale, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _kv_row(zi, h: int, hkv: int):
    """Grid row (b*h + head) -> folded kv row (b*hkv + head//group)."""
    return (zi // h) * hkv + (zi % h) // (h // hkv)


# The VMEM a one-kernel backward states (``vmem_limit_bytes``) wherever its
# resident accumulators and tiles fit it: twice a v5e's default scoped
# limit, a quarter of its VMEM.  ``flash_plan`` reads the backward's form
# from the shape against it, and whether the forward holds a kv row.
_FUSED_BWD_VMEM_LIMIT = 32 * 2 ** 20
# What a call that fits neither form in the limit above may still take
# as one kernel: the form that counts less, stating its own count (a
# whole MiB) and not the constant, up to three eighths of the VMEM.
# Above it the two passes, whose VMEM does not grow with S.
_FUSED_BWD_VMEM_CEILING = 48 * 2 ** 20
# The scoped VMEM the TPU compiler gives a kernel that states none.  A
# forward whose count fits it states nothing, so XLA schedules around it
# as around the call it was (what moves XLA's prefetch around a Pallas
# call is the VMEM the call states).
_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


# The SMEM a call's tile table may take (the int32 columns its kernels
# prefetch, ``_Q_MAJOR_COLUMNS`` or ``_K_MAJOR_COLUMNS`` a step): past it
# ``flash_plan`` keeps the rectangle.  LFM2's K-outermost backward, the
# largest table of any benchmark shape, is 4160 live tiles x 4 query
# heads a group x 6 columns: 390 KiB.
_TILE_TABLE_SMEM_LIMIT = 512 * 2 ** 10
_Q_MAJOR_COLUMNS = 3    # i, j, edges
_K_MAJOR_COLUMNS = 6    # j, g, i, edges, and the (g, i) that flushes next


def _tile_live(i, j, bq: int, bk: int, causal: bool, window: Optional[int],
               diffusion: Optional[tuple] = None):
    """Does Q tile ``i`` see K tile ``j``?  Causal: not if the K tile is
    entirely above the diagonal; with a window, not if it is entirely
    below the band either.  ``diffusion=(L, B)``, the block-diffusion
    mask over a noised copy of ``L`` rows and then the clean one (no tile
    straddles them): by the blocks of ``B`` the two tiles touch, each
    counted within its own copy, a noised Q tile sees a noised K tile
    that shares a block with it and a clean one that holds a strictly
    earlier block; a clean Q tile sees a clean K tile that holds its own
    or an earlier block, and no noised one.  One definition for the
    plan's table (Python ints) and for the kernels that keep the
    rectangle (traced): comparisons, products and shifts alone."""
    if diffusion is not None:
        half, block = diffusion
        shift = block.bit_length() - 1
        clean_q, clean_k = i * bq >= half, j * bk >= half
        q0, k0 = i * bq - half * clean_q, j * bk - half * clean_k
        q_first, q_last = q0 >> shift, (q0 + bq - 1) >> shift
        k_first, k_last = k0 >> shift, (k0 + bk - 1) >> shift
        same_copy = clean_q == clean_k
        return ((k_first <= q_last) & same_copy
                & (clean_q | (q_first <= k_last))
                | (k_first < q_last) & clean_k & (clean_q != clean_k))
    live = (j * bk <= (i + 1) * bq - 1) if causal else True
    if window is not None:
        live = live & ((j + 1) * bk - 1 >= i * bq - (window - 1))
    return live


def _plan_tile_live(plan: FlashPlan, i, j, s: int):
    """``_tile_live`` of a plan's tiles over ``s`` rows."""
    return _tile_live(
        i, j, plan.block_q, plan.block_k, plan.causal, plan.window,
        (s // 2, plan.block) if plan.mask == "block_diffusion" else None)


def _mask_tile(plan: FlashPlan, x, fill, i, j, s: int):
    """The transposed tile ``x`` [bk, bq] (keys on sublanes, queries on
    lanes) of Q tile ``i`` and K tile ``j`` with ``fill`` wherever the
    plan's mask hides the key from the query.  One definition for the
    forward and the three backward kernels."""
    if plan.mask == "full":
        return x
    bk, bq = x.shape
    if plan.mask == "block_diffusion":
        # block indices within each row's own copy, the keys' as a
        # column and the queries' as a row: a noised key is seen from
        # its own block (0 <= ahead <= 0), a clean key by a noised query
        # from strictly later blocks (ahead >= 1) and by a clean one
        # from its own and later ones (ahead >= 0)
        half, shift = s // 2, plan.block.bit_length() - 1
        clean_q, clean_k = i * bq >= half, j * bk >= half
        k_block = (j * bk - jnp.where(clean_k, half, 0)
                   + lax.broadcasted_iota(jnp.int32, (bk, 1), 0)) >> shift
        q_block = (i * bq - jnp.where(clean_q, half, 0)
                   + lax.broadcasted_iota(jnp.int32, (1, bq), 1)) >> shift
        ahead = q_block - k_block
        nearest = jnp.where(clean_k & jnp.logical_not(clean_q), 1, 0)
        farthest = jnp.where(clean_k, s, 0)
        return jnp.where((ahead >= nearest) & (ahead <= farthest), x, fill)
    k_pos = j * bk + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    q_pos = i * bq + lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    x = jnp.where(k_pos > q_pos, fill, x)
    if plan.window is not None:
        x = jnp.where(k_pos < q_pos - (plan.window - 1), fill, x)
    return x


class _Walk(NamedTuple):
    """How a grid's second axis walks one head's (or, K tile outermost,
    one kv row's) tiles.  ``tile`` and ``edges`` take the step ``t`` and
    the table's refs, as an index map gets them."""

    tables: tuple       # the int32 columns the call prefetches to SMEM
    steps: int          # the extent of the grid's second axis
    tile: Callable      # -> the step's tile: (i, j), or (j, g, i)
    edges: Callable     # -> the body's predicates, ``live`` last
    flushing: Optional[Callable] = None  # K-major: -> the (g, i) whose
    #                     dq block leaves VMEM next, at ``t`` or after


def _bit(edges, n: int):
    return ((edges >> n) & 1) == 1


def _run_edges(outer: np.ndarray) -> np.ndarray:
    """Bit 0 on the first step of every run of equal ``outer`` indices,
    bit 1 on the last."""
    turn = np.flatnonzero(np.diff(outer)) + 1
    edges = np.zeros_like(outer)
    edges[np.r_[0, turn]] += 1
    edges[np.r_[turn - 1, len(outer) - 1]] += 2
    return edges


def _q_major_table(live_tiles) -> np.ndarray:
    """``[3, T]``: a head's live tiles Q tile major, and each step's
    edges: bit 0 on the first live tile of its Q row, bit 1 on the
    last."""
    i, j = np.asarray(live_tiles, np.int32).T
    return np.stack([i, j, _run_edges(i)])


def _q_major_walk(plan: FlashPlan, nq: int, nk: int) -> _Walk:
    """Q tile outermost, K tiles innermost.  ``tile``: ``(i, j)``;
    ``edges``: the Q row's first step, its last, live."""
    if plan.live_tiles is None:
        def tile(t):
            return t // nk, t % nk

        def edges(t):
            i, j = tile(t)
            return j == 0, j == nk - 1, _plan_tile_live(
                plan, i, j, nq * plan.block_q)

        return _Walk((), nq * nk, tile, edges)
    table = _q_major_table(plan.live_tiles)

    def tile(t, qi, kj, _):
        return qi[t], kj[t]

    def edges(t, qi, kj, edge):
        return _bit(edge[t], 0), _bit(edge[t], 1), True

    return _Walk(tuple(table), table.shape[1], tile, edges)


def _k_major_table(live_tiles, nq: int, group: int) -> np.ndarray:
    """``[6, T]``: for each K tile ``j`` in turn, for each query head
    ``g`` of the group, the Q tiles ``i`` that see it: rows ``j``, ``g``,
    ``i``; the step's edges (bit 0 on the K tile's first step, bit 1 on
    its last, bit 2 where the step is the pair ``(g, i)``'s first live
    ``j``, bit 3 where it is its last); and the ``(g, i)`` of the next
    step at or after this one with bit 3 set, the pair whose dq block
    leaves VMEM next."""
    qi, kj = np.asarray(live_tiles, np.int32).T
    order = np.argsort(kj, kind="stable")       # K-major, i ascending
    seen = np.unique(kj, return_counts=True)[1]
    # every K tile's run of Q tiles, once a head of the group
    steps = np.concatenate([
        np.tile(run, group) for run in np.split(order, np.cumsum(seen)[:-1])])
    j, i = kj[steps], qi[steps]
    g = np.concatenate([np.repeat(np.arange(group, dtype=np.int32), n)
                        for n in seen])
    low, high = (np.full(nq, fill, np.int32) for fill in (j.max() + 1, -1))
    np.minimum.at(low, qi, kj)
    np.maximum.at(high, qi, kj)
    opens, closes = j == low[i], j == high[i]
    # the next closing step at or after each step: the last step closes
    nxt = np.flatnonzero(closes)[np.searchsorted(
        np.flatnonzero(closes), np.arange(len(j)))]
    edges = _run_edges(j) + (4 * opens + 8 * closes).astype(np.int32)
    return np.stack([j, g, i, edges, g[nxt], i[nxt]])


def _k_major_walk(plan: FlashPlan, nq: int, nk: int, group: int) -> _Walk:
    """K tile outermost, (query head in group, Q tile) pairs innermost.
    ``tile``: ``(j, g, i)``; ``edges``: the K tile's first step, its
    last, the pair's first K tile (dq's accumulator is zeroed), its last
    (dq's block is written), live."""
    if plan.live_tiles is None:
        pairs = group * nq

        def tile(t):
            return t // pairs, (t % pairs) // nq, t % nq

        def edges(t):
            j, _, i = tile(t)
            return (t % pairs == 0, t % pairs == pairs - 1, j == 0,
                    j == nk - 1, _plan_tile_live(plan, i, j,
                                                 nq * plan.block_q))

        def flushing(t):
            # dq's block index moves only in the last K tile's sweep
            j, g, i = tile(t)
            return (jnp.where(j == nk - 1, g, 0),
                    jnp.where(j == nk - 1, i, 0))

        return _Walk((), nk * pairs, tile, edges, flushing)
    table = _k_major_table(plan.live_tiles, nq, group)

    def tile(t, kj, qg, qi, *_):
        return kj[t], qg[t], qi[t]

    def edges(t, kj, qg, qi, edge, *_):
        e = edge[t]
        return _bit(e, 0), _bit(e, 1), _bit(e, 2), _bit(e, 3), True

    def flushing(t, kj, qg, qi, edge, fg, fi):
        return fg[t], fi[t]

    return _Walk(tuple(table), table.shape[1], tile, edges, flushing)


def _lanes(width: int) -> int:
    """A minor dimension padded to the 128 lanes its tiles occupy."""
    return -(-width // 128) * 128


def _whole_mib(count: int) -> int:
    """A byte count rounded up to a MiB: what a call states of its own."""
    return -(-count // 2 ** 20) * 2 ** 20


def _fused_bwd_vmem_bytes(s: int, d: int, bq: int, bk: int,
                          itemsize: int, dv: Optional[int] = None) -> int:
    """VMEM the one-kernel backward holds for a kv row of ``s`` keys with
    the Q tile outermost, every buffer's minor dimension padded to the
    128 lanes its tiles occupy, the keys' side (q, k, dq, dk) at head
    size ``d`` and the values' (v, do, dv) at ``dv`` (``None``: ``d``):
    the two float32 accumulators, the dk and dv output blocks (two
    buffers each), the streamed tiles (two buffers each), dq's
    accumulator and six score-sized float32 temporaries.  The
    TPU compiler asked 32.63 MiB for 16384 x 64 in bfloat16 inside a
    differentiated ``flash_attention`` (sandbox compile for a v5e,
    PR 29), where this counts 36.1; the unpadded count, 19.6, was wrong
    there."""
    keys, values = _lanes(d), _lanes(d if dv is None else dv)
    resident = s * (keys + values) * (4 + 2 * itemsize)
    tiles = 2 * ((2 * bq + bk) * keys + (bq + bk) * values) * itemsize
    return resident + tiles + d * bq * 4 + 6 * bk * bq * 4


def _dq_resident_bwd_vmem_bytes(s: int, d: int, bq: int, bk: int,
                                itemsize: int, group: int,
                                dv: Optional[int] = None) -> int:
    """VMEM the one-kernel backward holds with the K tile outermost: dq of
    a kv row's ``group`` query heads as ``[group * nq, d, bq]`` float32
    (channels on sublanes, queries on lanes), the K tile's two float32
    accumulators, the streamed tiles and output blocks (q, do, dq of
    ``bq`` rows, k, v, dk, dv of ``bk``; two buffers each, 128 lanes at
    least; v, do and dv at the value width ``dv``, ``None``: ``d``) and
    six score-sized float32 temporaries.  14.0 MiB for 8192
    keys at head size 256; the TPU compiler takes that shape inside a
    stated 14 MiB (sandbox compile for a v5e, PR 38)."""
    keys, values = _lanes(d), _lanes(d if dv is None else dv)
    dq_rows = group * (s // bq) * (-(-d // 8) * 8) * _lanes(bq)
    resident = dq_rows * 4 + bk * (keys + values) * 4
    tiles = 2 * ((2 * bq + 2 * bk) * keys
                 + (bq + 2 * bk) * values) * itemsize
    return resident + tiles + 6 * bk * bq * 4


def _fwd_resident_vmem_bytes(s: int, d: int, dv: int, bq: int, bk: int,
                             itemsize: int) -> int:
    """VMEM the forward holds with a kv row's K and V resident, every
    buffer's minor dimension padded to the 128 lanes its tiles occupy:
    the row's K and V blocks, the q and o tiles and the lse row (two
    buffers each), the float32 state (acc, m, l), the float32 copies of
    the three tiles the body reads and two score-sized float32
    temporaries.  The TPU compiler asks 17.31 MiB for 16384 keys of 128
    in bfloat16, where this counts 18.31, and 18.12 for 8192 of 256,
    where it counts 19.56 (sandbox compiles for a v5e on folded
    operands, PR 46)."""
    keys, values = _lanes(d), _lanes(dv)
    rows = 2 * s * (keys + values) * itemsize
    tiles = 2 * (bq * (keys + values) * itemsize + 8 * _lanes(bq) * 4)
    state = (-(-dv // 8) * 8 + 16) * _lanes(bq) * 4
    copies = (bq * keys + bk * (keys + values)) * 4
    return rows + tiles + state + copies + 2 * bk * _lanes(bq) * 4


def _flash_fwd_kernel(q, k, v, plan: FlashPlan, scale, interpret):
    """Returns (o [Z,S,DV], lse [Z,S]) with Z = batch*heads and DV the
    values' width, for folded operands and the call's ``plan``.

    Grid ``(z, steps)``: the second axis walks a head's live tiles Q
    tile major (``_q_major_walk``), K tiles innermost.  The
    online-softmax state (acc [dv, bq], m and l [1, bq]: transposed like
    the tile) persists across a Q row's steps in VMEM scratch, is set at
    the row's first live tile and flushed to the output block at its
    last; lse leaves as one row per Q tile.  GQA/MQA: k/v have Z_kv =
    batch*hkv rows; the index map routes each q head to its group.

    How K and V get to VMEM is the plan's ``fwd_kv_resident``.
    Wherever a kv row fits, its K and V are whole-row blocks ``(1, s,
    d)`` and ``(1, s, dv)`` whose block index moves once a kv row; the
    body slices the tile it needs.  The row is fetched from HBM once for
    all the grid steps that read it, where (1, bk, .) tiles would be
    fetched once a grid step, ``group x nq`` times over.  Above the
    limit the streamed tiles: only (1, bk, d) of K and (1, bk, dv) of V
    are resident per step and VMEM peak is O(bq*dv + bk*(d + dv)),
    independent of S (the long-context requirement).  Both forms run the
    same tile arithmetic in the same order: ``o`` and ``lse`` are equal
    to the bit.
    """
    z, s, d = q.shape
    dv = v.shape[-1]
    bq, bk, h, hkv = plan.block_q, plan.block_k, plan.heads, plan.kv_heads
    resident = plan.fwd_kv_resident
    nq, nk = s // bq, s // bk
    walk = _q_major_walk(plan, nq, nk)
    columns = len(walk.tables)

    def kernel(*refs):
        table = refs[:columns]
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = \
            refs[columns:]
        t = pl.program_id(1)
        i, j = walk.tile(t, *table)
        first, last, live = walk.edges(t, *table)

        @pl.when(first)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(live)
        def _compute():
            # the tile transposed: keys on sublanes, queries on lanes, so
            # a query's statistic is one lane of a (1, bq) row.  A row
            # whose keys are all masked so far sums placeholders (p = 1)
            # that corr wipes at its first live tile.
            rows = (pl.ds(pl.multiple_of(j * bk, bk), bk) if resident
                    else slice(None))      # a streamed block is the tile
            qb = q_ref[0].astype(jnp.float32) * scale  # [bq, d]
            kb = k_ref[0, rows, :].astype(jnp.float32)  # [bk, d]
            vb = v_ref[0, rows, :].astype(jnp.float32)
            st = _mask_tile(
                plan, jnp.dot(kb, qb.T, preferred_element_type=jnp.float32),
                NEG_INF, i, j, s)
            m_prev = m_ref[...]                        # [1, bq]
            m_new = jnp.maximum(m_prev, st.max(0, keepdims=True))
            p = jnp.exp(st - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + p.sum(0, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
                vb, p, _CONTRACT_ROWS, preferred_element_type=jnp.float32,
            )                                          # [dv, bq]
            m_ref[...] = m_new

        @pl.when(last)
        def _flush():
            o_ref[0] = (acc_ref[...] / l_ref[...]).T.astype(o_ref.dtype)
            lse_ref[0, 0] = m_ref[...] + jnp.log(l_ref[...])

    q_tile = lambda zi, t, *table: (zi, walk.tile(t, *table)[0], 0)

    def kv_block(width):
        # resident: the whole row, its block index moving once a kv row
        return pl.BlockSpec(
            (1, s if resident else bk, width),
            lambda zi, t, *table: (
                _kv_row(zi, h, hkv),
                0 if resident else walk.tile(t, *table)[1], 0))

    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=columns,
            grid=(z, walk.steps),
            in_specs=[
                pl.BlockSpec((1, bq, d), q_tile),
                kv_block(d),
                kv_block(dv),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, dv), q_tile),
                pl.BlockSpec((1, 1, 1, bq), lambda *at: (*q_tile(*at), 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((dv, bq), jnp.float32),  # acc
                pltpu.VMEM((1, bq), jnp.float32),   # running max m
                pltpu.VMEM((1, bq), jnp.float32),   # running sum l
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((z, s, dv), q.dtype),
            jax.ShapeDtypeStruct((z, nq, 1, bq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=plan.fwd_vmem_bytes or None,
        ),
        interpret=interpret,
        name="flash_fwd",
    )(*walk.tables, q, k, v)
    return o, lse.reshape(z, s)


def _flash_bwd_pallas(q, k, v, o, lse, do, plan: FlashPlan, scale,
                      interpret):
    """Pallas flash backward, tiled, walking the live tiles alone (a
    plain scan over K tiles, the tests' oracle, computes the whole
    upper triangle and streams O(S*bk) score tiles through HBM — on a
    causal LM that is ~2x wasted FLOPs and the dominant HBM stream).  P
    is recomputed from the forward's saved logsumexp; ``delta`` =
    rowsum(do*o) is the standard softmax-backward correction.

    One kernel (call site ``flash_bwd_dkdv``, which here covers dq, dk
    AND dv), each live tile's P and dS formed once, in the plan's
    ``bwd_form``; no partial sum goes through HBM in either.
    ``"dkdv_resident"`` (grid z, steps: ``_q_major_walk``, the
    forward's table), wherever
    ``_fused_bwd_vmem_bytes`` fits ``_FUSED_BWD_VMEM_LIMIT`` (or, past
    it in both forms, counts less than the other and fits
    ``_FUSED_BWD_VMEM_CEILING``, the call stating that count): Q tile
    fixed, K tiles stream.  dq accumulates as [d, bq], zeroed at the Q
    row's first live tile and turned once at its last; dk and dv
    accumulate in float32 scratch buffers of [S, d]
    and [S, dv] (the values' width: v, do, o and dv carry it, q, k, dq
    and dk the head size) that live for a whole kv row — under GQA the
    group's query heads are consecutive z and fold into them — zeroed at
    the row's first grid step and written, cast once, to the (1, S, d)
    and (1, S, dv) output blocks at its last.
    ``"dq_resident"`` (grid z_kv, steps: ``_k_major_walk``), where
    ``_dq_resident_bwd_vmem_bytes`` fits instead: K tile fixed, the
    (q-head-in-group, Q tile) pairs that see it stream; dk and dv of the
    tile accumulate as [bk, d] and [bk, dv] and flush at the tile's last
    pair, dq of the kv row's query heads as [group*nq, d, bq], each pair
    zeroed at its first live K tile and written, turned and cast, at its
    last (dq's block index is the pair that is written next, so it moves
    right after each write and each block goes to HBM once).  It adds
    the float32 terms in the order the two passes add them.

    Two passes above the ceiling in both forms, each recomputing P and
    dS, VMEM independent of S:
    Pass A (grid z_kv, steps; ``flash_bwd_dkdv``): the K-outermost
    kernel without dq.
    Pass B (grid z, steps; ``flash_bwd_dq``): Q tile fixed, K tiles
    stream; dq accumulates (as [d, bq], turned once at the flush).
    """
    z, s, d = q.shape
    z_kv, dv = k.shape[0], v.shape[-1]
    bq, bk, h, hkv = plan.block_q, plan.block_k, plan.heads, plan.kv_heads
    form, vmem_limit = plan.bwd_form, plan.bwd_vmem_bytes
    group = h // hkv
    nq, nk = s // bq, s // bk
    f32 = jnp.float32
    with_dq = form == "dq_resident"   # the K-outermost kernel takes dq too
    # delta is computed once per call and shared by all kernels, which
    # read it and lse as (1, bq) rows of a [Z, nq, 1, bq] view (a block
    # equal to the last two dims is legal for any bq).  Recomputing
    # delta per tile from an o tile costs a multiply and a cross-lane
    # sum per tile and one more DMA per grid step; delta and lse as
    # [Z, S, 128] lane-replicated arrays take that work out of the
    # kernels and give it back as larger DMAs per grid step and a second
    # broadcast.
    delta = (do.astype(f32) * o.astype(f32)).sum(-1)
    lse_r, delta_r = (x.reshape(z, nq, 1, bq) for x in (lse, delta))

    def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        i, j):
        """The shared backward recurrence: rebuild this tile's softmax P
        from the saved logsumexp and form dS = P * (dP - delta), both
        transposed like the forward's tile (keys on sublanes, queries
        on lanes).  One definition for all three kernels so the
        mask/scale math cannot drift."""
        qb = q_ref[0].astype(f32)
        kb = k_ref[0].astype(f32)
        vb = v_ref[0].astype(f32)
        dob = do_ref[0].astype(f32)
        st = jnp.dot(kb, qb.T, preferred_element_type=f32) * scale
        p = _mask_tile(plan, jnp.exp(st - lse_ref[0, 0]), 0.0, i, j, s)
        dp = jnp.dot(vb, dob.T, preferred_element_type=f32)
        ds = p * (dp - delta_ref[0, 0])
        return qb, kb, dob, p, ds

    # q, k, dq, dk are ``d`` wide; v, do, dv the values' own ``dv``
    k_spec = lambda tile, which: pl.BlockSpec((1, tile, d), which)
    v_spec = lambda tile, which: pl.BlockSpec((1, tile, dv), which)
    stat_spec = lambda which: pl.BlockSpec(
        (1, 1, 1, bq), lambda *at: (*which(*at), 0))

    def call(kernel, name, walk, rows, q_tile, kv_tile, out_specs,
             out_shape, scratch_shapes, semantics, vmem_limit=None):
        """A backward kernel over ``(rows, walk.steps)``: the walk's table,
        then q, k, v, do, lse and delta tiles in."""
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(walk.tables),
                grid=(rows, walk.steps),
                in_specs=[
                    k_spec(bq, q_tile),
                    k_spec(bk, kv_tile),
                    v_spec(bk, kv_tile),
                    v_spec(bq, q_tile),         # do
                    stat_spec(q_tile),          # lse
                    stat_spec(q_tile),          # delta
                ],
                out_specs=out_specs,
                scratch_shapes=scratch_shapes,
            ),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics,
                vmem_limit_bytes=vmem_limit or None),
            interpret=interpret,
            name=name,
        )(*walk.tables, q, k, v, do, lse_r, delta_r)

    # Q tile outermost (the forward's walk): the one kernel with dk and
    # dv resident, and the two passes' dq
    q_walk = _q_major_walk(plan, nq, nk)
    q_columns = len(q_walk.tables)
    q_outer = dict(
        walk=q_walk, rows=z,
        q_tile=lambda zi, t, *table: (zi, q_walk.tile(t, *table)[0], 0),
        kv_tile=lambda zi, t, *table: (
            _kv_row(zi, h, hkv), q_walk.tile(t, *table)[1], 0))

    def kernel_fused(*refs):
        table = refs[:q_columns]
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc) = refs[q_columns:]
        zi = pl.program_id(0)
        t = pl.program_id(1)
        i, j = q_walk.tile(t, *table)
        first, last, live = q_walk.edges(t, *table)

        # a kv row's query heads are consecutive zi: its accumulators
        # live from the first head's first tile to the last head's last
        @pl.when(jnp.logical_and(zi % group == 0, t == 0))
        def _init_row():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        @pl.when(first)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        @pl.when(live)
        def _compute():
            qb, kb, dob, p, ds = _recompute_p_ds(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, i, j
            )
            rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
            dv_acc[rows, :] += jnp.dot(p, dob, preferred_element_type=f32)
            dk_acc[rows, :] += jnp.dot(ds, qb,
                                       preferred_element_type=f32) * scale
            dq_acc[...] += lax.dot_general(
                kb, ds, _CONTRACT_ROWS, preferred_element_type=f32,
            ) * scale                                   # [d, bq]

        @pl.when(last)
        def _flush():
            dq_ref[0] = dq_acc[...].T.astype(dq_ref.dtype)

        @pl.when(jnp.logical_and(zi % group == group - 1,
                                 t == q_walk.steps - 1))
        def _flush_row():
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    if form == "dkdv_resident":
        kv_whole = lambda zi, t, *table: (_kv_row(zi, h, hkv), 0, 0)
        return call(
            kernel_fused, "flash_bwd_dkdv", **q_outer,
            out_specs=[k_spec(bq, q_outer["q_tile"]), k_spec(s, kv_whole),
                       v_spec(s, kv_whole)],
            out_shape=[jax.ShapeDtypeStruct((z, s, d), q.dtype),
                       jax.ShapeDtypeStruct((z_kv, s, d), k.dtype),
                       jax.ShapeDtypeStruct((z_kv, s, dv), v.dtype)],
            scratch_shapes=[pltpu.VMEM((d, bq), f32),
                            pltpu.VMEM((s, d), f32),
                            pltpu.VMEM((s, dv), f32)],
            # dk and dv accumulate across both axes
            semantics=("arbitrary", "arbitrary"), vmem_limit=vmem_limit)

    walk = _k_major_walk(plan, nq, nk, group)
    columns = len(walk.tables)

    def kernel_k_outer(*refs):
        """K tile fixed, the (q head in group, Q tile) pairs that see it
        stream: dk and dv of the tile, and ``with_dq`` dq too, from the
        same p and ds."""
        table = refs[:columns]
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs = \
            refs[columns:]
        if with_dq:
            dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc = refs
        else:
            dk_ref, dv_ref, dk_acc, dv_acc = refs
        t = pl.program_id(1)
        j, g, i = walk.tile(t, *table)
        first, last, opens, closes, live = walk.edges(t, *table)
        pair = g * nq + i

        @pl.when(first)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        if with_dq:
            @pl.when(opens)
            def _init_dq():
                dq_acc[pair] = jnp.zeros((d, bq), f32)

        @pl.when(live)
        def _compute():
            qb, kb, dob, p, ds = _recompute_p_ds(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, i, j
            )
            dv_acc[...] += jnp.dot(p, dob, preferred_element_type=f32)
            dk_acc[...] += jnp.dot(ds, qb,
                                   preferred_element_type=f32) * scale
            if with_dq:
                dq_acc[pair] += lax.dot_general(
                    kb, ds, _CONTRACT_ROWS, preferred_element_type=f32,
                ) * scale                               # [d, bq]

        @pl.when(last)
        def _flush():
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

        if with_dq:
            @pl.when(closes)
            def _flush_dq():
                dq_ref[0] = dq_acc[pair].T.astype(dq_ref.dtype)

    def q_row(zi, g):
        """The q row of kv row ``zi``'s query head ``g``."""
        return (zi // hkv) * h + (zi % hkv) * group + g

    def q_tile(zi, t, *table):
        _, g, i = walk.tile(t, *table)
        return (q_row(zi, g), i, 0)

    def dq_tile(zi, t, *table):
        # the pair written next: the index moves right after each write,
        # so each block goes to HBM once
        g, i = walk.flushing(t, *table)
        return (q_row(zi, g), i, 0)

    kv_tile = lambda zi, t, *table: (zi, walk.tile(t, *table)[0], 0)
    out_specs = [k_spec(bk, kv_tile), v_spec(bk, kv_tile)]
    out_shape = [jax.ShapeDtypeStruct((z_kv, s, d), k.dtype),
                 jax.ShapeDtypeStruct((z_kv, s, dv), v.dtype)]
    scratch_shapes = [pltpu.VMEM((bk, d), f32), pltpu.VMEM((bk, dv), f32)]
    if with_dq:
        out_specs.append(k_spec(bq, dq_tile))
        out_shape.append(jax.ShapeDtypeStruct((z, s, d), q.dtype))
        scratch_shapes.append(pltpu.VMEM((nq * group, d, bq), f32))
    dk, dvalues, *dq = call(
        kernel_k_outer, "flash_bwd_dkdv", walk, z_kv, q_tile, kv_tile,
        out_specs, out_shape, scratch_shapes, ("parallel", "arbitrary"),
        vmem_limit)     # with dq, what the plan states; else nothing
    if with_dq:
        return dq[0], dk, dvalues

    def kernel_dq(*refs):
        table = refs[:q_columns]
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs[q_columns:]
        t = pl.program_id(1)
        i, j = q_walk.tile(t, *table)
        first, last, live = q_walk.edges(t, *table)

        @pl.when(first)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        @pl.when(live)
        def _compute():
            _, kb, _, _, ds = _recompute_p_ds(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, i, j
            )
            dq_acc[...] += lax.dot_general(
                kb, ds, _CONTRACT_ROWS, preferred_element_type=f32,
            ) * scale                                   # [d, bq]

        @pl.when(last)
        def _flush():
            dq_ref[0] = dq_acc[...].T.astype(dq_ref.dtype)

    (dq,) = call(
        kernel_dq, "flash_bwd_dq", **q_outer,
        out_specs=[k_spec(bq, q_outer["q_tile"])],
        out_shape=[jax.ShapeDtypeStruct((z, s, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((d, bq), f32)],
        semantics=("parallel", "arbitrary"))
    return dq, dk, dvalues


# Below the kernels, so that no line of theirs moves (file and line of a
# Pallas kernel sit in its Mosaic payload: every flash call lowers to the
# text it did).
def unfolded(t, heads: int):
    """The ``[batch, seq, heads, head_dim]`` shape of a head-major ``t``
    ``[batch heads, seq, head_dim]``, for :func:`flash_plan`."""
    z, s, d = t.shape
    return jax.ShapeDtypeStruct((z // heads, s, heads, d), t.dtype)


def flash_attention_folded(q, k, v, *, heads: int, kv_heads: int,
                           scale: Optional[float] = None, **call):
    """:func:`flash_attention` for ``q`` ``[batch heads, seq, head_dim]``
    and ``k``, ``v`` ``[batch kv_heads, seq, .]`` that are head-major
    already (``ops/attn_prep.py`` writes them so); ``call`` the other
    keyword arguments of :func:`flash_plan`.  Returns ``[batch, seq,
    heads, value_dim]``."""
    plan = flash_plan(unfolded(q, heads), unfolded(k, kv_heads),
                      unfolded(v, kv_heads), **call)
    z, s, d = q.shape
    out = _flash(q, k, v, plan, scale if scale is not None else d ** -0.5,
                 bool(_interpret_for_backend(jax.default_backend())))
    return out.reshape(z // heads, heads, s, v.shape[2]).transpose(0, 2, 1, 3)

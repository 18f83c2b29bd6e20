"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference framework is data-parallel only (SURVEY.md §2.9/§5.7 — no
sequence parallelism exists in Horovod 0.19.1), but long-context scaling is
first-class in the TPU build: sequences longer than one chip's HBM are
sharded over a mesh axis and attention runs distributed.

Two schedules, both called inside ``shard_map`` over a sequence axis:

* :func:`ring_attention` — blockwise attention with an online softmax;
  K/V blocks rotate around the ring via ``lax.ppermute`` while each device
  keeps its Q shard.  Communication per step is one K/V block over ICI
  (neighbor exchange), overlapping with the block matmul — the TPU-native
  analog of Ring Attention (Liu et al.; see PAPERS.md), built on the same
  collective the Adasum VHDD uses.  Memory per device is O(S/P), enabling
  contexts P× longer than a single chip.

* :func:`ulysses_attention` — all-to-all resharding (DeepSpeed-Ulysses
  style): q/k/v flip from sequence-sharded to head-sharded with one
  ``lax.all_to_all``, attention runs *unpartitioned* per head, and the
  output flips back.  Two all-to-alls total; preferable when
  num_heads >= axis size and ICI all-to-all bandwidth is plentiful.

* :func:`ring_attention_zigzag` — the load-balanced causal ring.  A
  contiguous causal ring is latency-bound by its last rank (it attends at
  every step even though earlier ranks skip masked blocks); zigzag
  placement (rank i holds sequence chunks i and 2P-1-i) balances the
  triangle so EVERY rank computes exactly two half-size quadrant attends
  per ring step — ~2x less critical-path attention compute than the
  contiguous causal ring, with no masking inside the steady-state loop at
  all (the only masked compute is the self-chunk diagonal, handled once
  before the ring turns).

All are reverse-mode differentiable (scan + ppermute/all_to_all have
transpose rules), so they drop into a training step directly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "ring_attention",
    "ring_attention_zigzag",
    "ulysses_attention",
    "local_attention",
    "zigzag_positions",
    "zigzag_shard",
    "zigzag_unshard",
]


def _online_softmax_update(state, q_sub, k_sub, v_sub, scale, mask=None):
    """One online-softmax accumulation of ``q_sub`` (fp32) against a K/V
    block — the single definition of the m/l/o recurrence shared by the
    contiguous ring and the zigzag ring.  ``state`` is ``(o [b,sq,h,dv],
    m [b,h,sq], l [b,h,sq])`` in fp32, ``dv`` the values' own width
    (it need not be the keys'); ``mask`` is a bool ``[sq, sk]``
    (True = masked) used only for diagonal/partial blocks."""
    o, m, l = state
    if q_sub.shape[2] != k_sub.shape[2]:
        # GQA/MQA: k/v arrive at kv_heads and broadcast HERE — after any
        # ppermute — so ring interconnect traffic stays at kv width
        rep = q_sub.shape[2] // k_sub.shape[2]
        k_sub = jnp.repeat(k_sub, rep, axis=2)
        v_sub = jnp.repeat(v_sub, rep, axis=2)
    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q_sub, k_sub.astype(jnp.float32))
        * scale
    )
    if mask is not None:
        scores = jnp.where(mask[None, None], -jnp.inf, scores)
    m_new = jnp.maximum(m, scores.max(-1))
    # exp(-inf - -inf) can only arise for a q row with no unmasked key in
    # ANY block folded so far; both ring schedules fold the (diagonal-
    # masked) self block first, so m is finite from the first update on.
    p = jnp.exp(scores - m_new[..., None])
    corr = jnp.exp(m - m_new)  # [b,h,q]
    l = l * corr + p.sum(-1)
    o = (
        o * corr.transpose(0, 2, 1)[..., None]
        + jnp.einsum("bhqk,bkhd->bqhd", p, v_sub.astype(jnp.float32))
    )
    return o, m_new, l


def local_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset=0,
    kv_offset=0,
    window: Optional[int] = None,
    block_diffusion: Optional[int] = None,
) -> jax.Array:
    """Plain softmax attention on local (unpartitioned) q/k/v.

    Shapes ``[batch, seq, heads, head_dim]``.  ``q_offset``/``kv_offset``
    are the global positions of the first local row — the causal mask is
    computed in *global* coordinates so sharded callers get the right
    triangle.  The single-device reference that the distributed schedules
    must reproduce bit-for-bit (up to fp associativity).  ``window=W``
    (with ``causal``) lets a query see its last ``W`` keys, itself
    included: the flash kernel's window, as an explicit mask.
    ``block_diffusion=B`` (without ``causal``) is the flash kernel's
    block-diffusion mask, dense, over rows that hold a noised copy and
    then the clean one (:func:`block_diffusion_mask`); the offsets do
    not enter it.
    """
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} needs causal=True and window >= 1")
    if block_diffusion is not None and (causal or q.shape[1] != k.shape[1]):
        raise ValueError(
            "block_diffusion is a mask of its own over the same rows of "
            "q and k: it takes no causal=True")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        kv_pos = kv_offset + jnp.arange(k.shape[1])
        s = jnp.where(kv_pos[None, :] > q_pos[:, None], -jnp.inf, s)
        if window is not None:
            s = jnp.where(kv_pos[None, :] < q_pos[:, None] - (window - 1),
                          -jnp.inf, s)
    if block_diffusion is not None:
        s = jnp.where(block_diffusion_mask(q.shape[1], block_diffusion),
                      s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def block_diffusion_mask(rows: int, block: int) -> jax.Array:
    """``[rows, rows]`` booleans, true where row ``i`` sees key ``j`` in
    block-diffusion training (arXiv:2503.09573, the three-part mask):
    rows ``0 .. L-1`` are the noised copy of ``L = rows // 2`` tokens,
    rows ``L .. 2L-1`` the clean one, both in blocks of ``block``.  A
    noised row sees the noised rows of its own block and the clean rows
    of strictly earlier blocks; a clean row the clean rows of its own
    and of earlier blocks; no clean row a noised key."""
    if rows % 2 or (rows // 2) % block:
        raise ValueError(
            f"block_diffusion={block} must divide half of the {rows} rows "
            f"(a noised copy, then the clean one)")
    half = rows // 2
    at = jnp.arange(rows)
    clean = at >= half
    blk = (at - half * clean) // block
    qb, kb, qc, kc = blk[:, None], blk[None, :], clean[:, None], clean[None, :]
    return ((~qc & ~kc & (qb == kb)) | (~qc & kc & (kb < qb))
            | (qc & kc & (kb <= qb)))


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Ring attention over a sequence-sharded mesh axis.

    Call inside ``shard_map`` with q/k/v sharded along dim 1 (sequence)
    over ``axis_name``; shapes ``[batch, seq_local, heads, head_dim]``.
    Each of the P ring steps attends the local Q shard against one K/V
    block, folds the result into an online-softmax accumulator, and
    rotates the K/V block to the next neighbor with ``ppermute`` — the
    classic flash-attention recurrence, distributed.

    The causal mask is evaluated in global coordinates: at step t this
    rank holds the block originally owned by rank ``(me - t) % P``, so a
    whole block from a later rank masks to zero contribution and earlier
    blocks pass through unmasked.
    """
    size = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale_ = scale if scale is not None else d ** -0.5
    perm = [(j, (j + 1) % size) for j in range(size)]

    qf = q.astype(jnp.float32)
    q_pos = me * s_local + jnp.arange(s_local)

    def attend_block(operands):
        k_blk, v_blk, o, m, l, src = operands
        mask = None
        if causal:
            kv_pos = src * s_local + jnp.arange(s_local)
            mask = kv_pos[None, :] > q_pos[:, None]
        return _online_softmax_update(
            (o, m, l), qf, k_blk, v_blk, scale_, mask=mask
        )

    def step(carry, t):
        k_blk, v_blk, o, m, l = carry
        src = (me - t) % size  # original owner of the block in hand
        if causal:
            # A block from a later rank is ENTIRELY above the diagonal:
            # skip its einsums outright.  In this bulk-synchronous ring
            # the saving is FLOPs/energy, not wall-clock — every step
            # ends at the ppermute, and some rank (always the last)
            # attends at every step, so step latency is unchanged.  The
            # latency fix is load-balanced sequence placement:
            # ring_attention_zigzag, which gives every rank the same
            # per-step compute.  The diagonal-only mask refinement
            # (src == me) is deliberately not special-cased: the where
            # costs ~1/d of the einsum.
            o, m, l = lax.cond(
                src > me,
                lambda ops: (ops[2], ops[3], ops[4]),
                attend_block,
                (k_blk, v_blk, o, m, l, src),
            )
        else:
            o, m, l = attend_block((k_blk, v_blk, o, m, l, src))
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, o, m, l), None

    o0 = jnp.zeros((b, s_local, h, v.shape[3]), jnp.float32)
    m0 = jnp.full((b, h, s_local), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    (k_, v_, o, m, l), _ = lax.scan(
        step, (k, v, o0, m0, l0), jnp.arange(size)
    )
    del k_, v_, m
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _zigzag_order(size: int, seq: int):
    """Chunk permutation of the zigzag layout: [0, 2P-1, 1, 2P-2, ...]."""
    if seq % (2 * size):
        raise ValueError(f"sequence {seq} not divisible by 2*size={2 * size}")
    return [c for i in range(size) for c in (i, 2 * size - 1 - i)]


def _apply_chunk_order(x, order, axis):
    chunks = jnp.split(x, len(order), axis)
    return jnp.concatenate([chunks[c] for c in order], axis)


def zigzag_shard(x: jax.Array, size: int, axis: int = 0) -> jax.Array:
    """Reorder a GLOBAL sequence so a contiguous equal split over ``size``
    ranks gives each rank i the zigzag pair (chunk i, chunk 2*size-1-i).

    Feed the result through your normal sequence sharding (shard_map
    in_specs along ``axis``); pair with :func:`zigzag_unshard` on gathered
    outputs.  Sequence length must divide by 2*size."""
    return _apply_chunk_order(x, _zigzag_order(size, x.shape[axis]), axis)


def zigzag_unshard(x: jax.Array, size: int, axis: int = 0) -> jax.Array:
    """Inverse of :func:`zigzag_shard` on the same global view."""
    order = _zigzag_order(size, x.shape[axis])
    import numpy as _np

    return _apply_chunk_order(x, list(_np.argsort(order)), axis)


def zigzag_positions(axis_index, size: int, s_local: int) -> jax.Array:
    """Global token positions of rank ``axis_index``'s local rows under
    the zigzag layout (first half = chunk i, second half = chunk
    2*size-1-i)."""
    half = s_local // 2
    lo = axis_index * half + jnp.arange(half)
    hi = (2 * size - 1 - axis_index) * half + jnp.arange(half)
    return jnp.concatenate([lo, hi])


def ring_attention_zigzag(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Load-balanced CAUSAL ring attention over zigzag-placed sequences.

    Layout contract: the global sequence was passed through
    :func:`zigzag_shard` before sharding, so this rank's local rows are
    ``concat(chunk_me, chunk_{2P-1-me})`` in global order (positions from
    :func:`zigzag_positions`).  Outputs are in the same local layout;
    gather + :func:`zigzag_unshard` recovers global order.

    Why it balances: with contiguous placement the causal triangle gives
    rank P-1 work at every ring step while rank 0 idles after step 0.
    With the zigzag pair, quadrant (q-half x kv-half) visibility at step
    t (kv block originally from ``src = (me-t) % P``) is STATIC:

    - early-q vs late-kv: never visible (skipped by construction),
    - late-q  vs early-kv: always fully visible,
    - early-q vs early-kv: fully visible iff src < me,
    - late-q  vs late-kv:  fully visible iff src > me,

    so after the t=0 self-block (the only masked compute), every rank
    runs exactly TWO unmasked half-size attends per step.  Critical-path
    attention FLOPs are ~half the contiguous causal ring's and uniform
    across ranks.
    """
    size = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    if s_local % 2:
        raise ValueError("zigzag requires an even local sequence length")
    half = s_local // 2
    scale_ = scale if scale is not None else d ** -0.5
    perm = [(j, (j + 1) % size) for j in range(size)]
    qf = q.astype(jnp.float32)

    def accum(state, q_sub, k_sub, v_sub, mask=None):
        return _online_softmax_update(state, q_sub, k_sub, v_sub, scale_,
                                      mask=mask)

    def init_state():
        return (
            jnp.zeros((b, half, h, v.shape[3]), jnp.float32),
            jnp.full((b, h, half), -jnp.inf, jnp.float32),
            jnp.zeros((b, h, half), jnp.float32),
        )

    q_lo, q_hi = qf[:, :half], qf[:, half:]

    # t = 0: the self block — the ONLY masked compute in the schedule.
    tri = jnp.arange(half)[None, :] > jnp.arange(half)[:, None]  # k > q
    st_lo = accum(init_state(), q_lo, k[:, :half], v[:, :half], mask=tri)
    st_hi = accum(init_state(), q_hi, k[:, half:], v[:, half:], mask=tri)
    # late-q sees ALL of its own early chunk (me < 2P-1-me always)
    st_hi = accum(st_hi, q_hi, k[:, :half], v[:, :half])

    def step(carry, t):
        k_blk, v_blk, st_lo, st_hi = carry
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        src = (me - t) % size  # original owner of the block now in hand
        kc, vc = k_blk[:, :half], v_blk[:, :half]   # src's early chunk
        kd, vd = k_blk[:, half:], v_blk[:, half:]   # src's late chunk
        # exactly one of the two conds fires per step (src != me here)
        st_lo = lax.cond(
            src < me,
            lambda st: accum(st, q_lo, kc, vc),
            lambda st: st,
            st_lo,
        )
        st_hi = lax.cond(
            src > me,
            lambda st: accum(st, q_hi, kd, vd),
            lambda st: st,
            st_hi,
        )
        st_hi = accum(st_hi, q_hi, kc, vc)
        return (k_blk, v_blk, st_lo, st_hi), None

    (k_, v_, st_lo, st_hi), _ = lax.scan(
        step, (k, v, st_lo, st_hi), jnp.arange(1, size)
    )
    del k_, v_

    def finish(state):
        o, _, l = state
        return o / l.transpose(0, 2, 1)[..., None]

    out = jnp.concatenate([finish(st_lo), finish(st_hi)], axis=1)
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Ulysses-style sequence parallelism: reshard seq→heads, attend, flip
    back.

    Call inside ``shard_map`` with q/k/v sharded along dim 1 (sequence);
    shapes ``[batch, seq_local, heads, head_dim]`` with
    ``heads % axis_size == 0``.  One all-to-all turns the layout into
    full-sequence × heads/P, attention runs unpartitioned per head (the
    causal triangle needs no coordinate bookkeeping), and a second
    all-to-all restores sequence sharding.
    """
    size = lax.axis_size(axis_name)
    h = q.shape[2]
    if h % size != 0:
        raise ValueError(
            f"ulysses_attention requires heads ({h}) divisible by the "
            f"'{axis_name}' axis size ({size}); use ring_attention for "
            f"head counts smaller than the mesh axis."
        )

    def seq_to_heads(x):
        # [b, s/P, h, d] -> [b, s, h/P, d]
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    out = local_attention(
        seq_to_heads(q),
        seq_to_heads(k),
        seq_to_heads(v),
        causal=causal,
        scale=scale,
    )
    return heads_to_seq(out)

"""Pallas TPU flash attention.

The single-chip hot kernel under the transformer model family (and the
per-shard block compute of :mod:`horovod_tpu.parallel.ring_attention`).
The reference framework has no kernels of its own — its FLOPs live in
cuDNN via TF/torch; on TPU the idiomatic equivalent is a Pallas kernel
that keeps the (S, S) score matrix out of HBM entirely.

Design (the standard flash recurrence, TPU-shaped):

* Grid ``(batch*heads, S/block_q, S/block_k)``; each program owns one Q
  tile and one (block_k, d) K/V tile — the online-softmax state rides
  VMEM scratch across the sequential K grid dimension.  Wherever a kv
  row's K and V fit VMEM (``forward_plan``; every benchmark shape, up to
  30208 keys at head size 128 in bfloat16) the forward holds the row
  resident, fetched from HBM once for all the grid steps that read it,
  and slices its tile (PR 46); a longer row streams (1, block_k, d)
  tiles, one a grid step, and peak memory is O(block_q*d + block_k*d),
  independent of S.
* fp32 accumulators regardless of input dtype (bf16 in, bf16 out, fp32
  softmax state — the MXU-native mixed precision).
* Every kernel forms its score tile TRANSPOSED (``k @ q.T``: keys on
  sublanes, queries on lanes), so a per-query statistic (running max
  ``m``, running sum ``l``, the saved logsumexp, the backward's
  ``delta``) is one lane of a ``(1, block_q)`` row: it is reduced over
  sublanes with elementwise work, broadcast back the same way, and
  crosses HBM as ``[Z, S]``, a 2 KB row per Q tile.  Nothing in a tile
  loop goes through the cross-lane unit and no score-sized tile is
  ever transposed.  On a TPU v5e at 128 x 1024 x 64, bf16, causal,
  512 x 256 tiles, per call (chip runs of PR 26): forward 1.434 ms with
  the statistics as lane-replicated ``(block_q, 128)`` columns reduced
  and re-broadcast per tile, 0.750 with the columns reduced once per
  row block, 0.633 as rows; dk/dv 1.210 -> 0.973, dq 0.860 -> 0.679.
* Causal programs stop their K loop at the diagonal tile — the upper
  triangle is never computed, not just masked.
* Backward is a blockwise recompute from the saved logsumexp, wired via
  ``jax.custom_vjp`` so the op drops into training.  ``delta =
  rowsum(do * o)`` is computed once per call, outside the kernels
  (0.025 ms there), not once per tile.  ONE kernel forms each tile's
  ``p`` and ``ds`` once and takes dq, dk and dv from them; which sums
  stay resident in VMEM is read from the shape alone (``backward_plan``:
  what each form holds against ``_FUSED_BWD_VMEM_LIMIT``, the 32 MiB the
  call then states).  Q tile outermost, dq's accumulator and a whole kv
  row's dk and dv accumulators resident, wherever that fits (a head's
  channels fill whole 128-lane tiles, so 8192 keys at head size 64 or
  128 and 4096 keys at head size 256); else K tile outermost, the tile's
  dk and dv accumulators and the kv row's dq resident (8192 keys at head
  size 256, latent attention's shape in ``glm47f_train_s8192``, up to
  26624; 16384 keys at head size 64).  Where neither fits the 32 MiB,
  whichever of the two counts less, stating its own count, up to
  ``_FUSED_BWD_VMEM_CEILING``, 48 of the chip's 128 MiB (PR 44: 16384
  keys at head size 128 with seven query heads a key/value head,
  ``smallthinker_train_s16384``, Q tile outermost at 36.25 MiB; up to
  22016 keys there, 43008 at head size 256 with dq resident).  A longer
  sequence takes the two passes the one kernel replaced (dk/dv, then dq,
  each recomputing ``p`` and ``ds``), whose VMEM does not grow with S.
  Per call at 128 x 1024 x 64 (chip runs of PR 29): two passes 0.973 +
  0.679 ms, one kernel 1.025; at 32 query over 8 K/V heads x 8192 x 64,
  10.72 + 7.51 against 12.00.  At 20 x 8192 x 256 (chip runs of PR 38):
  two passes 21.21 ms, one kernel with dq resident 14.49 (2.66 us a
  needed tile for 3.90); the kv row's dk and dv in two spans of 4096
  keys with dq's float32 partials summed after the call 14.60 + 0.61, in
  four spans 16.25.  At 28 query over 4 K/V heads x 16384 x 128 (chip
  runs of PR 44): two passes 73.30 ms, one kernel stating 37 MiB 40.68
  (1.38 us a live tile for 2.48); banded at window 4096, 53.45 against
  25.21 (the two passes walk the 43 232 dead grid steps twice).
* The value width is the values' own (``dv = v.shape[-1]``; PR 42): ``v``,
  ``o``, ``do`` and dv carry it, ``q``, ``k``, dq and dk the head size
  ``d``, and the default scale stays ``d ** -0.5``.  The kernel bodies
  do not know the difference (``k q^T`` contracts ``d``; ``P V``, ``dp =
  v do^T`` and ``dv += p do`` contract or produce ``dv``): what reads it
  is the block shapes, the forward's ``acc`` as ``[dv, bq]``, the
  backward's dv accumulator (``[S, dv]`` with the Q tile outermost,
  ``[bk, dv]`` with the K tile outermost, beside dk's ``[S, d]`` /
  ``[bk, d]``; dq's ``[d, bq]`` rows keep ``d`` in every form) and the
  VMEM counts behind ``backward_form``, the dk and dv halves each at its
  own padded lanes (8192 keys of 64 with values of 128 count 8192 x
  64's 20.1 MiB: both widths pad to 128 lanes).  A call with ``dv == d``
  is the program it was, spec for spec.  The caller with two widths is
  differential attention (``models/transformer.py:
  _attend_differential``): 40 query rows of 64 on 20 key/value rows with
  values of 128, each score map formed once where a stacked call at
  head size 64 over 80 rows on 40 formed it twice.  At 1 x 8192, bf16,
  causal, per call alone with its layout (chip runs of PR 42): forward
  12.49 ms for the stacked call's 22.30, forward and backward 28.21 for
  52.90; banded at window 512, 8.21 for 14.08 and 17.10 for 29.98.  The
  same maps through ``dv == d`` kernels with ``q`` and ``k`` zero-padded
  to 128 channels read 12.45 and 28.95 (8.16 and 17.23 banded): keys of
  64 or of 128, a tile with values of 128 costs the same.  In the step
  of ``phi4mf_train_s8192`` the kernels alone: a full causal call 11.65
  ms forward and 15.68 backward (10 880 live 512 x 256 tiles: 1.07 and
  1.44 us each, where the stacked call's 21 760 cost 0.96 and 1.44), a
  banded call 6.55 and 8.72 (2 480 live tiles, and 18 000 dead grid
  steps at 0.22 and 0.29 us each).
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

import functools
from typing import Optional

import jax
import jax.numpy as jnp
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
    """
    b, s, h, d = q.shape
    if k.shape[:3] != v.shape[:3]:
        raise ValueError(
            f"flash_attention requires k and v matching in batch, sequence "
            f"and head count (the value width is v's own), got "
            f"{k.shape}/{v.shape}"
        )
    hkv = k.shape[2]
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
    scale_ = scale if scale is not None else d ** -0.5
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)
    if interpret is None:
        interpret = _interpret_for_backend(jax.default_backend())
    # [B,S,H,D] -> [B*H, S, D]: one grid row per (batch, head).  GQA/MQA:
    # k/v fold to [B*HKV, S, D] and the kernels' index maps route each q
    # head to its kv group — no broadcast materialization.
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(
        b * x.shape[2], s, x.shape[3]
    )
    out = _flash(fold(q), fold(k), fold(v), causal, scale_, bq, bk,
                 h, hkv, window, bool(interpret))
    return out.reshape(b, h, s, v.shape[3]).transpose(0, 2, 1, 3)


def tile_counts(rows: int, seq: int, block_q: int, block_k: int, *,
                causal: bool, window: Optional[int] = None):
    """``(live, grid)``: of the ``(q, k)`` tiles one call's grid walks
    (``rows`` batch x head rows of ``seq`` keys, tiles as ``_pick_block``
    makes them), how many the kernels' ``needed`` predicate admits.  The
    grid is whole whatever the mask: a dead tile costs its grid step and
    no arithmetic, and a DMA of K and V tiles only where those stream
    (the one-kernel backward's, and the forward's of a kv row too long
    to stay resident: ``forward_plan``).  Plain Python on shapes, for a
    counter set while a step is traced."""
    bq, bk = _pick_block(seq, block_q), _pick_block(seq, block_k)
    nq, nk = seq // bq, seq // bk
    if window is not None and window >= seq:
        window = None
    live = 0
    for i in range(nq):
        for j in range(nk):
            needed = j * bk <= (i + 1) * bq - 1 if causal else True
            if window is not None:
                needed = needed and (j + 1) * bk - 1 >= i * bq - (window - 1)
            live += needed
    return rows * live, rows * nq * nk


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


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, scale, bq, bk, h, hkv, window, interpret):
    o, _ = _flash_fwd_kernel(q, k, v, causal, scale, bq, bk, h, hkv,
                             window, interpret)
    return o


def _flash_fwd(q, k, v, causal, scale, bq, bk, h, hkv, window,
               interpret):
    o, lse = _flash_fwd_kernel(q, k, v, causal, scale, bq, bk, h, hkv,
                               window, interpret)
    # named here, not at the call site: the residual has to be the named
    # value, or a rematerialised block reruns the kernel to get it
    o = checkpoint_name(o, scopes.FLASH_OUT)
    lse = checkpoint_name(lse, scopes.FLASH_LSE)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, bq, bk, h, hkv, window, interpret, res,
               do):
    q, k, v, o, lse = res
    return _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale, bq, bk,
                             h, hkv, window, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _kv_row(zi, h: int, hkv: int):
    """Grid row (b*h + head) -> folded kv row (b*hkv + head//group)."""
    return (zi // h) * hkv + (zi % h) // (h // hkv)


# The VMEM a one-kernel backward states (``vmem_limit_bytes``) wherever its
# resident accumulators and tiles fit it: twice a v5e's default scoped
# limit, a quarter of its VMEM.  ``backward_plan`` reads the form from
# the shape against it.
_FUSED_BWD_VMEM_LIMIT = 32 * 2 ** 20
# What a call that fits neither form in the limit above may still take
# as one kernel (PR 44): the form that counts less, stating its own count
# (a whole MiB) and not the constant, up to three eighths of the VMEM.
# Above it the two passes, whose VMEM does not grow with S.
_FUSED_BWD_VMEM_CEILING = 48 * 2 ** 20


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


def backward_plan(seq: int, head_dim: int, group: int, itemsize: int,
                  block_q: int = 512, block_k: int = 256,
                  value_dim: Optional[int] = None):
    """``(form, vmem_limit_bytes)``: which backward a call of this shape
    runs and the VMEM its one kernel states, from the shape alone
    (``group`` query heads a key/value head, tiles as ``_pick_block``
    makes them, values ``value_dim`` wide: ``None`` says as wide as the
    keys).
    ``"dkdv_resident"``: one kernel, Q tile outermost, a kv row's dk and
    dv accumulators resident (PR 29's), wherever it fits
    ``_FUSED_BWD_VMEM_LIMIT``.  ``"dq_resident"``: one kernel, K tile
    outermost, the group's dq rows resident, where that fits the limit
    instead.  Both then state the limit, so a call that fit it before
    PR 44 is the program it was.  Above both, whichever of the two
    counts less, if that fits ``_FUSED_BWD_VMEM_CEILING``, stating its
    own count rounded up to a MiB (16384 keys at head size 128 with
    seven query heads a key/value head: 36.25 MiB with the Q tile
    outermost, ``smallthinker_train_s16384``).  ``"two_passes"`` above
    that, which state nothing (0).
    ``_flash_bwd_pallas`` branches on it and ``models/transformer.py``
    sets its gauges from it while a step is traced."""
    bq, bk = _pick_block(seq, block_q), _pick_block(seq, block_k)
    q_outer = _fused_bwd_vmem_bytes(seq, head_dim, bq, bk, itemsize,
                                    value_dim)
    k_outer = _dq_resident_bwd_vmem_bytes(seq, head_dim, bq, bk, itemsize,
                                          group, value_dim)
    if q_outer <= _FUSED_BWD_VMEM_LIMIT:
        return "dkdv_resident", _FUSED_BWD_VMEM_LIMIT
    if k_outer <= _FUSED_BWD_VMEM_LIMIT:
        return "dq_resident", _FUSED_BWD_VMEM_LIMIT
    count, form = min((q_outer, "dkdv_resident"), (k_outer, "dq_resident"))
    if count <= _FUSED_BWD_VMEM_CEILING:
        return form, _whole_mib(count)
    return "two_passes", 0


def backward_form(*shape, **tiles) -> str:
    """The form alone of ``backward_plan`` (same arguments)."""
    return backward_plan(*shape, **tiles)[0]


# The scoped VMEM the TPU compiler gives a kernel that states none.  A
# forward whose count fits it states nothing, so XLA schedules around it
# as around the call it was (PR 31: what moves XLA's prefetch around a
# Pallas call is the VMEM the call states).
_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


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


def forward_plan(seq: int, head_dim: int, value_dim: int, itemsize: int,
                 block_q: int = 512, block_k: int = 256):
    """``(resident, vmem_limit_bytes)``: how the forward of this shape
    holds K and V and the VMEM its call states, from the shape alone
    (tiles as ``_pick_block`` makes them).  Resident: a kv row's K and V
    whole in VMEM, fetched once a row (under GQA once for the group's
    query heads, ``group x nq x nk`` grid steps), wherever the count
    fits ``_FUSED_BWD_VMEM_LIMIT``; above it (1, block_k, .) tiles
    stream, one a grid step, VMEM independent of S.  The call states a
    limit only where the count passes the compiler's default scoped
    limit, and then the count rounded up to a MiB (0: nothing stated).
    ``_flash_fwd_kernel`` branches on it and ``models/transformer.py``
    sets its gauges from it while a step is traced."""
    bq, bk = _pick_block(seq, block_q), _pick_block(seq, block_k)
    count = _fwd_resident_vmem_bytes(seq, head_dim, value_dim, bq, bk,
                                     itemsize)
    if count > _FUSED_BWD_VMEM_LIMIT:
        return False, 0
    if count <= _DEFAULT_SCOPED_VMEM:
        return True, 0
    return True, _whole_mib(count)


def _flash_fwd_kernel(q, k, v, causal, scale, bq, bk, h, hkv, window,
                      interpret):
    """Returns (o [Z,S,DV], lse [Z,S]) with Z = batch*heads and DV the
    values' width.

    Grid ``(z, nq, nk)``, K tiles innermost, whole whatever the mask.
    The online-softmax state (acc [dv, bq], m and l [1, bq]: transposed
    like the tile) persists across the sequential K dimension in VMEM
    scratch and is flushed to the output block at the last K tile; lse
    leaves as one row per Q tile.  GQA/MQA: k/v have Z_kv = batch*hkv
    rows; the index map routes each q head to its group.

    How K and V get to VMEM is read from the shape (``forward_plan``).
    Wherever a kv row fits, its K and V are whole-row blocks ``(1, s,
    d)`` and ``(1, s, dv)`` whose block index moves once a kv row; the
    body slices the tile it needs.  The row is fetched from HBM once for
    the ``group x nq x nk`` grid steps that read it, where (1, bk, .)
    tiles were fetched once a grid step, live or dead, ``group x nq``
    times over (PR 46), and a dead grid step copies nothing.  Above the
    limit the streamed tiles: only (1, bk, d) of K and (1, bk, dv) of V
    are resident per step and VMEM peak is O(bq*dv + bk*(d + dv)),
    independent of S (the long-context requirement).  Both forms run the
    same tile arithmetic in the same order: ``o`` and ``lse`` are equal
    to the bit.
    """
    z, s, d = q.shape
    dv = v.shape[-1]
    nq, nk = s // bq, s // bk
    resident, vmem_limit = forward_plan(s, d, dv, q.dtype.itemsize, bq, bk)

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref):
        i = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        # Causal: K tiles strictly above the diagonal contribute
        # nothing; with a window, tiles entirely below the band are dead
        # too — skip both (a dead step of the resident form costs its
        # grid step alone, of the streamed form its tiles' DMA too).
        needed = (j * bk <= (i + 1) * bq - 1) if causal else (j >= 0)
        if window is not None:
            needed = jnp.logical_and(
                needed, (j + 1) * bk - 1 >= i * bq - (window - 1)
            )

        @pl.when(needed)
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
            st = jnp.dot(kb, qb.T, preferred_element_type=jnp.float32)
            if causal:
                k_pos = j * bk + lax.broadcasted_iota(
                    jnp.int32, (bk, bq), 0
                )
                q_pos = i * bq + lax.broadcasted_iota(
                    jnp.int32, (bk, bq), 1
                )
                st = jnp.where(k_pos > q_pos, NEG_INF, st)
                if window is not None:
                    st = jnp.where(k_pos < q_pos - (window - 1),
                                   NEG_INF, st)
            m_prev = m_ref[...]                        # [1, bq]
            m_new = jnp.maximum(m_prev, st.max(0, keepdims=True))
            p = jnp.exp(st - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * corr + p.sum(0, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
                vb, p, _CONTRACT_ROWS, preferred_element_type=jnp.float32,
            )                                          # [dv, bq]
            m_ref[...] = m_new

        @pl.when(j == nk - 1)
        def _flush():
            o_ref[0] = (acc_ref[...] / l_ref[...]).T.astype(o_ref.dtype)
            lse_ref[0, 0] = m_ref[...] + jnp.log(l_ref[...])

    def kv_block(width):
        # resident: the whole row, its block index moving once a kv row
        return pl.BlockSpec(
            (1, s if resident else bk, width),
            lambda zi, qi, ki: (_kv_row(zi, h, hkv), 0 if resident else ki, 0))

    o, lse = pl.pallas_call(
        kernel,
        grid=(z, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda zi, qi, ki: (zi, qi, 0)),
            kv_block(d),
            kv_block(dv),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda zi, qi, ki: (zi, qi, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda zi, qi, ki: (zi, qi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((z, s, dv), q.dtype),
            jax.ShapeDtypeStruct((z, nq, 1, bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dv, bq), jnp.float32),  # acc
            pltpu.VMEM((1, bq), jnp.float32),   # running max m
            pltpu.VMEM((1, bq), jnp.float32),   # running sum l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit or None,
        ),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return o, lse.reshape(z, s)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale, bq, bk,
                      h, hkv, window, interpret):
    """Pallas flash backward, tiled, skipping fully-masked causal blocks
    (the scan fallback below computes the whole upper triangle and
    streams O(S*bk) score tiles through HBM — on a causal LM that is ~2x
    wasted FLOPs and the dominant HBM stream).  P is recomputed from the
    forward's saved logsumexp; ``delta`` = rowsum(do*o) is the standard
    softmax-backward correction.

    One kernel (call site ``flash_bwd_dkdv``, which here covers dq, dk
    AND dv), each needed tile's P and dS formed once, in the form
    ``backward_form`` reads from the shape; no partial sum goes through
    HBM in either.
    ``"dkdv_resident"`` (grid z, nq, nk), wherever
    ``_fused_bwd_vmem_bytes`` fits ``_FUSED_BWD_VMEM_LIMIT`` (or, past
    it in both forms, counts less than the other and fits
    ``_FUSED_BWD_VMEM_CEILING``, the call stating that count): Q tile
    fixed, K tiles stream.  dq accumulates as [d, bq], turned once at
    the flush; dk and dv accumulate in float32 scratch buffers of [S, d]
    and [S, dv] (the values' width: v, do, o and dv carry it, q, k, dq
    and dk the head size) that live for a whole kv row — under GQA the
    group's query heads are consecutive z and fold into them — zeroed at
    the row's first grid step and written, cast once, to the (1, S, d)
    and (1, S, dv) output blocks at its last.
    ``"dq_resident"`` (grid z_kv, nk, nq*group), where
    ``_dq_resident_bwd_vmem_bytes`` fits instead: K tile fixed,
    (q-head-in-group, Q tile) pairs stream; dk and dv of the tile
    accumulate as [bk, d] and [bk, dv] and flush at the last pair, dq of
    the kv row's
    query heads as [group*nq, d, bq], zeroed in the first K tile's
    sweep and written, turned and cast, in the last's (dq's block index
    stays put until then, so each block goes to HBM once).  It adds the
    float32 terms in the order the two passes add them.  Per call at
    128 x 1024 x 64 this orientation read 1.093 ms where the other reads
    1.025, and 13.08 against 12.00 at 8192 keys with grouped heads (chip
    runs of PR 29: dq there as a whole-row output block); at 20 x 8192 x
    256, where the other does not fit, 14.49 ms against the two passes'
    21.21, the same stating 16, 24 or 32 MiB (chip runs of PR 38).

    Two passes above the ceiling in both forms, each recomputing P and
    dS, VMEM independent of S:
    Pass A (grid z_kv, nk, nq*group; ``flash_bwd_dkdv``): the
    K-outermost kernel without dq.
    Pass B (grid z, nq, nk; ``flash_bwd_dq``): Q tile fixed, K tiles
    stream; dq accumulates (as [d, bq], turned once at the flush).
    """
    z, s, d = q.shape
    z_kv, dv = k.shape[0], v.shape[-1]
    group = h // hkv
    nq, nk = s // bq, s // bk
    f32 = jnp.float32
    form, vmem_limit = backward_plan(s, d, group, q.dtype.itemsize, bq, bk,
                                     dv)
    with_dq = form == "dq_resident"   # the K-outermost kernel takes dq too
    # delta is computed once per call and shared by all kernels, which
    # read it and lse as (1, bq) rows of a [Z, nq, 1, bq] view (a block
    # equal to the last two dims is legal for any bq).  What the chip
    # said of the alternatives (PR 26, per call at 128 x 1024 x 64):
    # recomputing delta per tile from an o tile cost a multiply and a
    # cross-lane sum per tile and one more 64 KB DMA per grid step;
    # delta and lse as [Z, S, 128] lane-replicated arrays took that work
    # out of the kernels and gave it back as 256 KB DMAs per dk/dv grid
    # step and a second 64 MiB broadcast (kernels 2.069 -> 2.085 ms, the
    # whole backward 2.687 -> 2.769); as rows 1.652 and 2.062.
    delta = (do.astype(f32) * o.astype(f32)).sum(-1)
    lse_r, delta_r = (x.reshape(z, nq, 1, bq) for x in (lse, delta))

    def _tile_needed(i, j):
        """Does Q tile ``i`` see K tile ``j``?  Causal: not if the K tile
        is entirely above the diagonal; with a window, not if it is
        entirely below the band either.  The forward's predicate, one
        definition for all three backward kernels."""
        needed = (j * bk <= (i + 1) * bq - 1) if causal else (j >= 0)
        if window is not None:
            needed = jnp.logical_and(
                needed, (j + 1) * bk - 1 >= i * bq - (window - 1)
            )
        return needed

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
        p = jnp.exp(st - lse_ref[0, 0])
        if causal:
            k_pos = j * bk + lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            q_pos = i * bq + lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            p = jnp.where(k_pos > q_pos, 0.0, p)
            if window is not None:
                p = jnp.where(k_pos < q_pos - (window - 1), 0.0, p)
        dp = jnp.dot(vb, dob.T, preferred_element_type=f32)
        ds = p * (dp - delta_ref[0, 0])
        return qb, kb, dob, p, ds

    def kernel_k_outer(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       *refs):
        """K tile fixed, (q head in group, Q tile) pairs stream: dk and dv
        of the tile, and ``with_dq`` dq too, from the same p and ds."""
        if with_dq:
            dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc = refs
        else:
            dk_ref, dv_ref, dk_acc, dv_acc = refs
        j = pl.program_id(1)
        t = pl.program_id(2)          # (q head in group) * nq + (q tile)
        i = t % nq

        @pl.when(t == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        if with_dq:
            @pl.when(j == 0)
            def _init_dq():
                dq_acc[t] = jnp.zeros((d, bq), f32)

        @pl.when(_tile_needed(i, j))
        def _compute():
            qb, kb, dob, p, ds = _recompute_p_ds(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, i, j
            )
            dv_acc[...] += jnp.dot(p, dob, preferred_element_type=f32)
            dk_acc[...] += jnp.dot(ds, qb,
                                   preferred_element_type=f32) * scale
            if with_dq:
                dq_acc[t] += lax.dot_general(
                    kb, ds, _CONTRACT_ROWS, preferred_element_type=f32,
                ) * scale                               # [d, bq]

        @pl.when(t == nq * group - 1)
        def _flush():
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

        if with_dq:
            @pl.when(j == nk - 1)
            def _flush_dq():
                dq_ref[0] = dq_acc[t].T.astype(dq_ref.dtype)

    def kernel_dq(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  dq_ref, dq_acc):
        i = pl.program_id(1)
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        @pl.when(_tile_needed(i, j))
        def _compute():
            _, kb, _, _, ds = _recompute_p_ds(
                q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, i, j
            )
            dq_acc[...] += lax.dot_general(
                kb, ds, _CONTRACT_ROWS, preferred_element_type=f32,
            ) * scale                                   # [d, bq]

        @pl.when(j == nk - 1)
        def _flush():
            dq_ref[0] = dq_acc[...].T.astype(dq_ref.dtype)

    def kernel_fused(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc):
        zi = pl.program_id(0)
        i = pl.program_id(1)
        j = pl.program_id(2)
        # a kv row's query heads are consecutive zi: its accumulators
        # live from the first head's first tile to the last head's last
        first_tile = jnp.logical_and(i == 0, j == 0)
        last_tile = jnp.logical_and(i == nq - 1, j == nk - 1)

        @pl.when(jnp.logical_and(zi % group == 0, first_tile))
        def _init_row():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

        @pl.when(j == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

        @pl.when(_tile_needed(i, j))
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

        @pl.when(j == nk - 1)
        def _flush():
            dq_ref[0] = dq_acc[...].T.astype(dq_ref.dtype)

        @pl.when(jnp.logical_and(zi % group == group - 1, last_tile))
        def _flush_row():
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    # q, k, dq, dk are ``d`` wide; v, do, dv the values' own ``dv``
    k_spec = lambda tile, which: pl.BlockSpec((1, tile, d), which)
    v_spec = lambda tile, which: pl.BlockSpec((1, tile, dv), which)
    stat_spec = lambda which: pl.BlockSpec((1, 1, 1, bq), which)

    if form == "dkdv_resident":
        q_tile = lambda zi, ii, ji: (zi, ii, 0)
        q_stat = lambda zi, ii, ji: (zi, ii, 0, 0)
        kv_tile = lambda zi, ii, ji: (_kv_row(zi, h, hkv), ji, 0)
        kv_whole = lambda zi, ii, ji: (_kv_row(zi, h, hkv), 0, 0)
        return pl.pallas_call(
            kernel_fused,
            grid=(z, nq, nk),
            in_specs=[
                k_spec(bq, q_tile),
                k_spec(bk, kv_tile),
                v_spec(bk, kv_tile),
                v_spec(bq, q_tile),         # do
                stat_spec(q_stat),          # lse
                stat_spec(q_stat),          # delta
            ],
            out_specs=[
                k_spec(bq, q_tile),
                k_spec(s, kv_whole),
                v_spec(s, kv_whole),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((z, s, d), q.dtype),
                jax.ShapeDtypeStruct((z_kv, s, d), k.dtype),
                jax.ShapeDtypeStruct((z_kv, s, dv), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((d, bq), f32),
                pltpu.VMEM((s, d), f32),
                pltpu.VMEM((s, dv), f32),
            ],
            compiler_params=pltpu.CompilerParams(
                # dk and dv accumulate across all three axes
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
                vmem_limit_bytes=vmem_limit,
            ),
            interpret=interpret,
            name="flash_bwd_dkdv",
        )(q, k, v, do, lse_r, delta_r)

    def _qrow(zi, ti):
        """The K-outermost q row for kv row ``zi`` and inner step ``ti``."""
        return (zi // hkv) * h + (zi % hkv) * group + ti // nq

    q_tile = lambda zi, ji, ti: (_qrow(zi, ti), ti % nq, 0)
    q_stat = lambda zi, ji, ti: (_qrow(zi, ti), ti % nq, 0, 0)
    kv_tile = lambda zi, ji, ti: (zi, ji, 0)

    def dq_tile(zi, ji, ti):
        # dq's block index moves only in the last K tile's sweep, where
        # the kernel writes it: each block goes to HBM once
        last = jnp.where(ji == nk - 1, ti, 0)
        return (_qrow(zi, last), last % nq, 0)

    out_specs = [k_spec(bk, kv_tile), v_spec(bk, kv_tile)]
    out_shape = [jax.ShapeDtypeStruct((z_kv, s, d), k.dtype),
                 jax.ShapeDtypeStruct((z_kv, s, dv), v.dtype)]
    scratch_shapes = [pltpu.VMEM((bk, d), f32), pltpu.VMEM((bk, dv), f32)]
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )
    if with_dq:
        out_specs.append(k_spec(bq, dq_tile))
        out_shape.append(jax.ShapeDtypeStruct((z, s, d), q.dtype))
        scratch_shapes.append(pltpu.VMEM((nq * group, d, bq), f32))
        compiler_params = pltpu.CompilerParams(
            # dq accumulates across the K tiles too
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit,
        )
    dk, dvalues, *dq = pl.pallas_call(
        kernel_k_outer,
        grid=(z_kv, nk, nq * group),
        in_specs=[
            k_spec(bq, q_tile),
            k_spec(bk, kv_tile),
            v_spec(bk, kv_tile),
            v_spec(bq, q_tile),         # do
            stat_spec(q_stat),          # lse
            stat_spec(q_stat),          # delta
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=compiler_params,
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(q, k, v, do, lse_r, delta_r)
    if with_dq:
        return dq[0], dk, dvalues
    (dq,) = pl.pallas_call(
        kernel_dq,
        grid=(z, nq, nk),
        in_specs=[
            k_spec(bq, lambda zi, ii, ji: (zi, ii, 0)),
            k_spec(bk, lambda zi, ii, ji: (_kv_row(zi, h, hkv), ji, 0)),
            v_spec(bk, lambda zi, ii, ji: (_kv_row(zi, h, hkv), ji, 0)),
            v_spec(bq, lambda zi, ii, ji: (zi, ii, 0)),
            stat_spec(lambda zi, ii, ji: (zi, ii, 0, 0)),
            stat_spec(lambda zi, ii, ji: (zi, ii, 0, 0)),
        ],
        out_specs=[k_spec(bq, lambda zi, ii, ji: (zi, ii, 0))],
        out_shape=[jax.ShapeDtypeStruct((z, s, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((d, bq), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse_r, delta_r)
    return dq, dk, dvalues


def _flash_bwd_blockwise(q, k, v, o, lse, do, causal, scale, bk,
                         window=None):
    """Blockwise flash backward (pure JAX scan over K tiles) — kept as the
    differential reference for the Pallas backward (tests pin equality)
    and as a debugging fallback.  ``v`` and ``do`` may be wider or
    narrower than ``q`` and ``k``: dv comes back at the values' width.
    """
    z, s, d = q.shape
    nk = s // bk
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    dof, of = do.astype(jnp.float32), o.astype(jnp.float32)
    delta = (dof * of).sum(-1)  # [Z,S]
    q_pos = jnp.arange(s)

    def body(dq, j):
        kb = lax.dynamic_slice_in_dim(kf, j * bk, bk, axis=1)
        vb = lax.dynamic_slice_in_dim(vf, j * bk, bk, axis=1)
        st = jnp.einsum("zqd,zkd->zqk", qf, kb) * scale
        p = jnp.exp(st - lse[..., None])  # exact softmax: exp(s-m)/l
        if causal:
            k_pos = j * bk + jnp.arange(bk)
            p = jnp.where(k_pos[None, :] > q_pos[:, None], 0.0, p)
            if window is not None:
                p = jnp.where(
                    k_pos[None, :] < q_pos[:, None] - (window - 1),
                    0.0, p,
                )
        dp = jnp.einsum("zqd,zkd->zqk", dof, vb)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("zqk,zkd->zqd", ds, kb) * scale
        dk_j = jnp.einsum("zqk,zqd->zkd", ds, qf) * scale
        dv_j = jnp.einsum("zqk,zqd->zkd", p, dof)
        return dq, (dk_j, dv_j)

    dq, (dks, dvs) = lax.scan(
        body, jnp.zeros_like(qf), jnp.arange(nk)
    )
    # stacked [nk, Z, bk, D] -> [Z, S, D], D the keys' or the values'
    unfold = lambda t: t.transpose(1, 0, 2, 3).reshape(z, s, t.shape[-1])
    return (
        dq.astype(q.dtype),
        unfold(dks).astype(k.dtype),
        unfold(dvs).astype(v.dtype),
    )

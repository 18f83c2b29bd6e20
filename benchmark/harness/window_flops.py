"""Operations and bytes of flash attention under a sliding window
(``horovod_tpu/ops/flash_attention.py`` with ``window=W``: a query sees
its last ``W`` keys, itself included), computed from shapes: what
``benchmark/metrics/swa_flash_roofline.py`` divides by the chip's peaks,
and the banded part of ``benchmark/models/afmoe.py``'s model FLOPs.

The count is of visible (query, key) PAIRS, not of the tiles a kernel
walks: a kernel computes whole tiles, so its share of this bound stays
under 100 % by construction, the further the more of its tiles straddle
the band's edges.
"""

from __future__ import annotations

from typing import Optional


def visible_pairs(seq_len: int, window: Optional[int] = None) -> int:
    """(query, key) pairs a causal mask admits in one sequence of
    ``seq_len``, each query seeing at most its last ``window`` keys: the
    first ``window`` queries see 1, 2, ... ``window`` keys, every later
    one ``window``.  No window, or one of ``seq_len`` or more: the causal
    triangle."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def swa_train_flops_bytes(batch: int, heads: int, kv_heads: int,
                          seq_len: int, head_dim: int, window: Optional[int],
                          layers: int, dtype_bytes: int = 2):
    """(flops, bytes) one training step's windowed flash-attention calls
    need, forward and backward, over ``layers`` layers, on one chip.

    Operations: the algorithm's seven matmuls per (sequence, query head)
    over the visible pairs, ``2 x pairs x head_dim`` each: QK^T and PV
    forward; recomputed QK^T, dP = dO V^T, dV, dK and dQ backward (the
    flash backward has to recompute the scores: that one recompute is
    the algorithm, further ones are the implementation's).  Bytes:
    forward reads q and writes o, backward reads q, o and dO and writes
    dq, six ``seq_len x head_dim`` arrays a QUERY head; forward reads k
    and v, backward reads them again and writes dk and dv, six arrays a
    KEY/VALUE head; each moved once (the row statistics are
    ``1 / head_dim`` of that and left out)."""
    pairs = visible_pairs(seq_len, window)
    flops = 7 * 2 * pairs * head_dim * batch * heads * layers
    array = seq_len * head_dim * dtype_bytes
    nbytes = 6 * array * (heads + kv_heads) * batch * layers
    return flops, nbytes

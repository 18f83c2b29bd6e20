"""Operations and bytes of differential attention's flash calls
(arXiv:2410.05258 through ``horovod_tpu/ops/flash_attention.py``),
computed from shapes: what ``benchmark/metrics/diff_flash_roofline.py``
divides by the chip's peaks, and the attention part of
``benchmark/models/phi4flash.py``'s model FLOPs.

A layer of ``heads`` query sub-heads of ``head_dim`` has ``heads`` score
maps (two a pair) over ``head_dim`` channels, and each map reads values
``2 x head_dim`` wide (the pair's).  The count is the algorithm's: a
program that makes a map twice to read the two halves of its values
(four calls at one head size) does more, and reads under what one pass
with wide values can.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness.window_flops import visible_pairs


def forward_flops(seq_len: int, window: Optional[int], heads: int,
                  head_dim: int) -> float:
    """Operations one sequence of one layer needs, forward: per visible
    (query, key) pair and score map, ``q k^T`` over ``head_dim`` channels
    and ``P V`` over ``2 x head_dim``, a multiply-add two operations."""
    return 2 * visible_pairs(seq_len, window) * heads * 3 * head_dim


def diff_train_flops_bytes(batch: int, seq_len: int, heads: int,
                           kv_heads: int, head_dim: int, windows,
                           dtype_bytes: int = 2):
    """(flops, bytes) one training step's differential flash calls need,
    forward and backward, on one chip.  ``windows`` gives each layer's
    window (None: the causal triangle), a cross layer's among them.

    Operations: seven matmuls' worth for a training step as
    ``window_flops`` reckons them, here three over the score channels
    (``q k^T`` forward, recomputed, and with ``dS`` for ``dq`` and
    ``dk``: four of ``head_dim``) and three over the values' (``P V``,
    ``dP = dO V^T``, ``dV``: three of ``2 x head_dim``): ``(4 + 3 x 2) /
    3`` of the forward's ``1 + 2``.  Bytes, each array once: forward
    reads q and writes o, backward reads q, o and dO and writes dq, six
    ``seq_len x head_dim`` arrays a query sub-head; k and v are read
    forward and backward and dk, dv written, six arrays a key/value head,
    in a cross layer too (it reads the shared pair and writes its share
    of the pair's gradient)."""
    flops = sum(forward_flops(seq_len, w, heads, head_dim)
                for w in windows) * (4 + 3 * 2) / 3 * batch
    array = seq_len * head_dim * dtype_bytes
    nbytes = 6 * array * (heads + kv_heads) * batch * len(windows)
    return flops, nbytes

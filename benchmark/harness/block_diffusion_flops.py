"""Operations and bytes of flash attention under the block-diffusion
training mask (``horovod_tpu/ops/flash_attention.py`` with
``block_diffusion=B``: the ``2L`` rows hold a noised copy of ``L`` tokens
and then the clean one, both in blocks of ``B``), computed from shapes:
what ``benchmark/metrics/bd_flash_roofline.py`` divides by the chip's
peaks, and the attention part of ``benchmark/models/sdar_moe.py``'s model
FLOPs.

The count is of visible (query, key) PAIRS, the algorithm and not the
calls: a kernel computes whole tiles, so its share of this bound stays
under 100 % by construction, and it reads the same work whatever
implements the mask.
"""

from __future__ import annotations


def visible_pairs(length: int, block: int) -> int:
    """(query, key) pairs the mask shows in one sequence of ``2 *
    length`` rows, from its equation: a noised row sees the ``block``
    noised keys of its own block and the clean keys of the blocks before
    it (``block * b`` for block ``b``); a clean row the clean keys up to
    its own block's end (``block * (b + 1)``); no clean row a noised
    key."""
    blocks = length // block
    noised_to_noised = length * block
    noised_to_clean = block * block * blocks * (blocks - 1) // 2
    clean_to_clean = block * block * blocks * (blocks + 1) // 2
    return noised_to_noised + noised_to_clean + clean_to_clean


def bd_train_flops_bytes(batch: int, heads: int, kv_heads: int,
                         length: int, head_dim: int, block: int,
                         layers: int, dtype_bytes: int = 2):
    """(flops, bytes) one training step's flash-attention calls under
    the mask need, forward and backward, over ``layers`` layers, on one
    chip; ``length`` is ``L``, the data tokens a sequence (the calls run
    ``2L`` rows).

    Operations: the algorithm's seven matmuls per (sequence, query head)
    over the visible pairs, ``2 x pairs x head_dim`` each: QK^T and PV
    forward; recomputed QK^T, dP = dO V^T, dV, dK and dQ backward.
    Bytes: forward reads q and writes o, backward reads q, o and dO and
    writes dq, six ``2L x head_dim`` arrays a QUERY head; forward reads k
    and v, backward reads them again and writes dk and dv, six arrays a
    KEY/VALUE head; each moved once."""
    pairs = visible_pairs(length, block)
    flops = 7 * 2 * pairs * head_dim * batch * heads * layers
    array = 2 * length * head_dim * dtype_bytes
    nbytes = 6 * array * (heads + kv_heads) * batch * layers
    return flops, nbytes

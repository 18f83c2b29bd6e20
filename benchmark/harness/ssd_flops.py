"""Operations and bytes of the Mamba-2 chunked state-space scan
(``horovod_tpu/ops/ssd.py``), computed from shapes: what
``benchmark/metrics/ssd_roofline.py`` divides by the chip's peaks, and the
scan's part of ``benchmark/models/granite_hybrid.py``'s model FLOPs.
"""

from __future__ import annotations


def ssd_forward_macs_per_token(heads: int, head_dim: int, groups: int,
                               state: int, chunk: int) -> float:
    """Multiply-adds one token of one layer's scan needs, forward.

    The chunked algorithm (state-space duality) has four products.  Per
    chunk of ``L`` tokens, with ``N = state`` and ``P = head_dim``:

    * ``C B^T``, the ``L x L`` scores of a group: only ``j <= i`` is
      used, the causal half, ``L^2 N / 2`` multiply-adds a group;
    * the masked, decayed scores times ``x``: again the causal half,
      ``L^2 P / 2`` a head;
    * the state a chunk adds, ``B^T (decay * dt * x)``: ``L N P`` a head;
    * the read-out of the state a chunk starts from, ``C S``: ``L N P`` a
      head.

    Divided by ``L`` that is, per token, ``L N / 2`` a group and
    ``L P / 2 + 2 N P`` a head.  The decays, the cumulated sums, the
    recurrence over the chunk states (elementwise, one step a chunk) and
    the skip ``D x`` are not matmuls and are left out, as the softmax is
    for attention: the count is the least the algorithm needs, so a share
    of the roofline computed from it cannot pass 100 %."""
    return (groups * chunk * state / 2
            + heads * (chunk * head_dim / 2 + 2 * state * head_dim))


def ssd_train_flops_bytes(batch: int, seq_len: int, heads: int,
                          head_dim: int, groups: int, state: int,
                          chunk: int, layers: int, dtype_bytes: int = 2):
    """(flops, bytes) one training step's scans need, forward and
    backward, over ``layers`` Mamba layers, on one chip.

    Operations: ``ssd_forward_macs_per_token``, two operations a
    multiply-add, backward twice the forward (each product has two
    gradients), nothing recomputed.  Bytes: forward reads ``x``
    (heads x head_dim a token), ``dt`` (one float32 a head), ``B`` and
    ``C`` (groups x state each) and writes ``y`` (like ``x``), each once;
    backward reads those five again, ``dy`` in the place of ``y``, and
    writes the four gradients ``dx``, ``d dt``, ``dB``, ``dC`` once.  The
    chunk states (heads x head_dim x state float32 a chunk, 1/chunk of a
    token's share) stay on the chip in the best case and are left out."""
    tokens = batch * seq_len * layers
    flops = 3 * 2 * ssd_forward_macs_per_token(
        heads, head_dim, groups, state, chunk) * tokens
    wide = heads * head_dim * dtype_bytes          # x, y, dy, dx
    narrow = 2 * groups * state * dtype_bytes      # B and C, or dB and dC
    dt = heads * 4
    forward = 2 * wide + narrow + dt
    backward = 2 * wide + narrow + dt + wide + narrow + dt
    return flops, (forward + backward) * tokens

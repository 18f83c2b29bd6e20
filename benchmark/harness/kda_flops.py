"""Operations and bytes of the chunked gated delta rule with a decay per
channel (Kimi Delta Attention, ``horovod_tpu/ops/kda.py``), computed from
shapes: what ``benchmark/metrics/kda_scan_roofline.py`` divides by the
chip's peaks, and the rule's part of ``benchmark/models/kimi_linear.py``'s
model FLOPs.  Also what a latent-attention call whose keys are wider
than its values needs (``nope_mla_flash_roofline``).

The count is of the ALGORITHM at the configuration's chunk and not of
the calls: it reads the same work whether XLA's matmuls or a Pallas
kernel compute it, and an implementation that forms a product twice, or
takes the triangular inverse by squarings, reads lower, never higher.
"""

from __future__ import annotations

import math


def kda_forward_macs_per_token(heads: int, d_k: int, d_v: int,
                               chunk: int) -> float:
    """Multiply-adds one token of one layer's rule needs, forward.

    Per head and chunk of ``C`` tokens from the chunk's starting state
    ``S`` (``d_k x d_v``):

    * the two Gram matrices ``sum_d a_id k_jd exp(G_id - G_jd)``, for
      ``a = k`` below the diagonal and ``a = q`` on and below it: the
      causal halves, ``C^2 d_k`` together;
    * ``(I + A)^-1`` of a unit lower triangular ``C x C`` matrix: by
      substitution ``C^3 / 6`` (by ``log2 C`` squarings ``2 (log2 C - 1)
      C^3``: never fewer, so substitution is what is counted);
    * ``W = T (K e^G)`` and ``U = T V`` with ``T`` lower triangular:
      ``C^2 (d_k + d_v) / 2``;
    * ``U~ = U - W S``, the read-out ``(q e^G) S`` and the state's
      update ``(k e^(G_C - G))^T U~``: ``C d_k d_v`` each;
    * ``A_qk U~``, the causal half: ``C^2 d_v / 2``.

    The decays, the cumulated sums and the gates are elementwise and
    left out, as the softmax is for attention: the count is the least
    the algorithm needs, so a share of the roofline computed from it
    cannot pass 100 %."""
    c = chunk
    substitution = c ** 3 / 6
    squarings = 2 * max(0, int(math.log2(c)) - 1) * c ** 3
    a_chunk = (c * c * d_k + min(substitution, squarings)
               + c * c * (d_k + d_v) / 2 + 3 * c * d_k * d_v
               + c * c * d_v / 2)
    return heads * a_chunk / c


def kda_train_flops_bytes(batch: int, seq_len: int, heads: int, d_k: int,
                          d_v: int, chunk: int, layers: int,
                          dtype_bytes: int = 2):
    """(flops, bytes) one training step's rules need, forward and
    backward, over ``layers`` layers, on one chip.

    Operations: ``kda_forward_macs_per_token``, two operations a
    multiply-add, backward twice the forward (each product has two
    gradients), nothing recomputed.  Bytes: forward reads ``q``, ``k``
    (``d_k`` a head and token each), ``v`` (``d_v``), the float32
    log-decays ``g`` (``d_k``) and ``beta`` (one) and writes ``o``
    (``d_v``), each once; backward reads those five again, ``do`` in the
    place of ``o``, and writes the five gradients once.  The kept states
    (``d_k x d_v`` float32 every few chunks) stay on the chip in the best
    case and are left out."""
    tokens = batch * seq_len * layers
    flops = 3 * 2 * kda_forward_macs_per_token(heads, d_k, d_v,
                                               chunk) * tokens
    inputs = heads * ((2 * d_k + d_v) * dtype_bytes + 4 * d_k + 4)
    out = heads * d_v * dtype_bytes
    return flops, (2 * (inputs + out) + inputs) * tokens


def unequal_flash_train_flops_bytes(batch: int, heads: int, seq_len: int,
                                    qk_dim: int, v_dim: int, layers: int,
                                    dtype_bytes: int = 2):
    """(flops, bytes) one training step's full causal flash calls need
    where a head's keys are ``qk_dim`` wide and its values ``v_dim``, no
    grouping, over ``layers`` layers: the visible pairs
    (``harness/window_flops.py:visible_pairs``) times the algorithm's
    seven matmuls, four over the key channels (QK^T, its one recompute,
    dK, dQ) and three over the value channels (PV, dP = dO V^T, dV), as
    ``harness/diff_attn_flops.py`` counts 64 and 128.  Bytes: q, k and
    their gradients three ``seq_len x qk_dim`` arrays a head each
    (read forward, read backward, gradient written), v likewise and o,
    o again and dO at ``v_dim``: six arrays of each width a head."""
    from benchmark.harness import window_flops

    pairs = window_flops.visible_pairs(seq_len)
    n = batch * heads * layers
    flops = 2 * pairs * (4 * qk_dim + 3 * v_dim) * n
    nbytes = 6 * seq_len * (qk_dim + v_dim) * dtype_bytes * n
    return flops, nbytes

"""Operations and bytes of the chunked gated delta rule whose decay is
ONE number a head and token, with fewer key heads than value heads (Gated
DeltaNet, ``horovod_tpu/ops/kda.py:gated_delta_rule``), computed from
shapes: what ``benchmark/metrics/gdn_scan_roofline.py`` divides by the
chip's peaks, and the rule's part of ``benchmark/models/qwen3_next.py``'s
model FLOPs.

The count is of the scalar-decay ALGORITHM at the configuration's chunk
and not of the calls.  Its multiply-adds are
``harness/kda_flops.py:kda_forward_macs_per_token``'s at the value heads'
count: the products are the same in both rules (two Gram matrices, the
triangular inverse, ``W``, ``U``, the three products with the state, the
read-out), the scalar rule only leaves the decays out of the Gram
matrices' operands, which were elementwise and never counted.  Its bytes
are its own: ``g`` and ``beta`` one float32 a value head and token, ``q``
and ``k`` at the key heads' count.  So it reads the same work whatever
implements the rule: today's entry, which spreads ``g`` over a head's
channels and ``q`` and ``k`` over the value heads for the channel-decay
kernels, moves more than this and reads lower; a rule specialised to the
scalar decay reads higher, never past 100 %.
"""

from __future__ import annotations

from benchmark.harness import kda_flops


def gdn_train_flops_bytes(batch: int, seq_len: int, key_heads: int,
                          value_heads: int, d_k: int, d_v: int, chunk: int,
                          layers: int, dtype_bytes: int = 2):
    """(flops, bytes) one training step's rules need, forward and
    backward, over ``layers`` layers, on one chip.

    Operations: ``kda_forward_macs_per_token`` at ``value_heads`` heads
    (a state a value head), two operations a multiply-add, backward twice
    the forward, nothing recomputed.  Bytes: forward reads ``q`` and
    ``k`` (``d_k`` a KEY head and token each), ``v`` (``d_v`` a value
    head), the float32 ``g`` and ``beta`` (one each a value head) and
    writes ``o`` (``d_v``), each once; backward reads those five again,
    ``do`` in the place of ``o``, and writes the five gradients once.
    The kept states stay on the chip in the best case and are left
    out."""
    tokens = batch * seq_len * layers
    flops = 3 * 2 * kda_flops.kda_forward_macs_per_token(
        value_heads, d_k, d_v, chunk) * tokens
    inputs = (key_heads * 2 * d_k * dtype_bytes
              + value_heads * (d_v * dtype_bytes + 4 + 4))
    out = value_heads * d_v * dtype_bytes
    return flops, (2 * (inputs + out) + inputs) * tokens

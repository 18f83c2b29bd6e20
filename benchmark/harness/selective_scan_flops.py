"""Operations and bytes of the Mamba-1 selective scan
(``horovod_tpu/ops/selective_scan.py``), computed from shapes: what
``benchmark/metrics/sscan_roofline.py`` divides by the chip's peaks, and
the scan's part of ``benchmark/models/phi4flash.py``'s model FLOPs.  The
count is the recurrence's, and does not know how a kernel walks it.
"""

from __future__ import annotations

# Per token, channel and state, forward: the decay's argument ``dt A``,
# its ``exp``, the recurrence's multiply-add ``a h + b`` with ``b = (dt u)
# B`` one multiply, and the read-out's multiply-add ``y += C h``.
FORWARD_OPS = 1 + 1 + 2 + 1 + 2
# Backward, the counterpart and nothing recomputed: two gradients for
# each forward product (``dC`` and ``dh`` of the read-out; ``dB`` and
# ``d(dt u)`` of ``b``; ``da`` and the state's gradient carried on through
# ``a``; ``d dt`` and ``dA`` of ``dt A`` through the ``exp``), a
# multiply-add each: twice the forward's.
BACKWARD_OPS = 2 * FORWARD_OPS


def forward_flops_per_token(channels: int, state: int) -> float:
    """Operations one token of one layer's scan needs, forward."""
    return FORWARD_OPS * channels * state


def sscan_train_flops_bytes(batch: int, seq_len: int, channels: int,
                            state: int, layers: int, dtype_bytes: int = 2):
    """(flops, bytes) one training step's selective scans need, forward
    and backward, over ``layers`` layers, on one chip.

    Bytes, each array once: forward reads ``u`` (``dtype_bytes`` a
    channel), ``dt`` (float32 a channel), ``B`` and ``C`` (``state``
    each) and writes ``y``; backward reads those and ``dy`` and writes
    ``du``, ``d dt``, ``dB`` and ``dC``.  ``A`` and ``D`` are a layer's,
    not a token's, and the states a kernel keeps between its passes stay
    on the chip in the best case: both left out."""
    tokens = batch * seq_len * layers
    flops = (FORWARD_OPS + BACKWARD_OPS) * channels * state * tokens
    wide = channels * dtype_bytes                  # u, y, dy, du
    narrow = 2 * state * dtype_bytes               # B and C, or dB and dC
    dt = channels * 4
    forward = 2 * wide + dt + narrow
    backward = (2 * wide + dt + narrow) + (wide + dt + narrow)
    return flops, (forward + backward) * tokens

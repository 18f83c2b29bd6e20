"""Bytes of a gated short convolution's elementwise chain
(``horovod_tpu/models/transformer.py:short_conv_mixer``, scope
``short_conv_filter``: ``z = C * conv(B * u)``, the conv a causal
depthwise filter of a few taps), computed from shapes: what
``benchmark/metrics/short_conv_filter_roofline.py`` divides by the
chip's memory bandwidth.  The chain has no matmul and a handful of
operations an element (a product, a multiply-add a tap, a product), so
the memory side bounds it by an order of magnitude and the operations
are left out.
"""

from __future__ import annotations


def filter_train_bytes(batch: int, seq_len: int, channels: int,
                       layers: int, dtype_bytes: int = 2) -> int:
    """Bytes one training step's chains need to move, forward and
    backward, over ``layers`` conv layers, on one chip.  Forward reads
    ``B``, ``C`` and ``u`` (the three thirds of ``in_proj``'s output) and
    writes ``z``: four ``[batch, seq_len, channels]`` arrays.  Backward
    reads those three again and ``dz`` and writes ``dB``, ``dC`` and
    ``du``: seven.  Each moved once in the compute dtype; the taps and
    their gradient are ``taps x channels`` and left out, as is a
    rematerialised forward (a recompute is the implementation's)."""
    return (4 + 7) * batch * seq_len * channels * dtype_bytes * layers

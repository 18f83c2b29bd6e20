"""Bytes of the hyper-connections' mixing
(``horovod_tpu/models/hyper_connections.py``: the read-out ``u = sum_j
H_pre[j] X[j]`` under the scope ``hc_read`` and the write-back ``X'[i] =
sum_j H_res[i, j] X[j] + H_post[i] y`` under ``hc_write``), computed from
shapes: what ``benchmark/metrics/hc_mix_roofline.py`` divides by the
chip's memory bandwidth.  Both are a few multiply-adds an element of a
stream that is ``n`` activations wide, so the memory side bounds them by
an order of magnitude and the operations are left out.  The maps
themselves (``n^2 + 2 n`` floats a token) are a thousandth of the stream
and left out too; making them (the norm, the projection, Sinkhorn) is
another scope, ``hc_coeff``, and not counted here.
"""

from __future__ import annotations


def mix_forward_elements(tokens: int, channels: int, streams: int) -> int:
    """Elements one sub-layer's mixing moves forward: the read-out reads
    the ``streams`` copies and writes ``u``; the write-back reads the
    copies again and the branch's output ``y`` and writes the copies:
    ``(3 streams + 2)`` arrays of ``tokens x channels``."""
    return (3 * streams + 2) * tokens * channels


def mix_train_bytes(batch: int, seq_len: int, channels: int, streams: int,
                    sublayers: int, dtype_bytes: int = 2) -> int:
    """Bytes one training step's mixing needs to move over ``sublayers``
    sub-layers (two a block) on one chip, every array once in the
    stream's item size.  Four times the forward: the forward itself; the
    forward again, because a rematerialised block keeps its input alone
    and the backward of ``sum_j H[j] X[j]`` needs every ``X[j]`` and
    ``y`` again (a block that kept them instead would write and read
    them once more: the same traffic); and the backward twice that, each
    array of the forward read again beside a gradient of its size
    written (``dX``, ``du``, ``dy``) or read (``dX'``).  The count is of
    the algorithm, whatever implements it: a kernel that fuses the
    read-out into the norm, or the three maps' gradients into one pass,
    moves no less."""
    return (4 * mix_forward_elements(batch * seq_len, channels, streams)
            * dtype_bytes * sublayers)

"""Operations and bytes of a dropless expert layer whose experts have no
gate matrix, ``W_down act(W_up x)``
(``horovod_tpu/parallel/moe.py:grouped_ffn`` on a first matrix ``[held,
hidden, width]``), computed from shapes and from the rows the program's
counter says were routed to the experts held here: what
``benchmark/metrics/ungated_experts_roofline.py`` divides by the chip's
peaks, and the experts' part of ``benchmark/models/nemotron_h.py``'s
model FLOPs.  ``harness/moe_flops.py`` counts three matrices an expert
and would read such a layer 1.5 times over its share.
"""

from __future__ import annotations


def expert_forward_macs_per_row(hidden: int, width: int) -> float:
    """Multiply-adds one row needs in one ungated expert, forward: up
    (``hidden x width``) and down (``width x hidden``).  The activation
    is no matmul and is left out."""
    return 2.0 * hidden * width


def experts_train_flops_bytes(rows: float, hidden: int, width: int,
                              held: int, layers: int,
                              dtype_bytes: int = 2):
    """(flops, bytes) one training step's grouped matmuls need, forward
    and backward, over ``layers`` expert layers whose held experts got
    ``rows`` rows in all (the counter's sum over layers), on one chip.

    Operations: ``expert_forward_macs_per_row``, two operations a
    multiply-add, backward twice the forward (each product has two
    gradients), nothing recomputed, and nothing for a row whose expert
    lives elsewhere.  Bytes: forward reads a row (``hidden``) and writes
    its output (``hidden``) once and reads each held expert's two
    matrices once; backward reads the row and its output's gradient,
    writes the row's gradient, reads the matrices again and writes their
    gradients once.  The ``width``-wide intermediates stay on the chip in
    the best case and are left out."""
    flops = 3 * 2 * expert_forward_macs_per_row(hidden, width) * rows
    row = hidden * dtype_bytes
    matrices = layers * held * 2 * hidden * width * dtype_bytes
    return flops, (2 + 3) * row * rows + 3 * matrices

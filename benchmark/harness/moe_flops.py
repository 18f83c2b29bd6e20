"""Operations and bytes of a dropless expert layer's grouped matmuls
(``horovod_tpu/parallel/moe.py:grouped_ffn``), computed from shapes and
from the rows the program's counter says were routed to the experts held
here: what ``benchmark/metrics/moe_experts_roofline.py`` divides by the
chip's peaks, and the routed experts' part of
``benchmark/models/glm4_moe_lite.py``'s model FLOPs.
"""

from __future__ import annotations


def expert_forward_macs_per_row(hidden: int, width: int) -> float:
    """Multiply-adds one routed row needs in one gated expert, forward:
    gate and up (``hidden x 2 width``) and down (``width x hidden``).
    The silu and the product are not matmuls and are left out."""
    return 3.0 * hidden * width


def experts_train_flops_bytes(rows: float, hidden: int, width: int,
                              held: int, layers: int,
                              dtype_bytes: int = 2):
    """(flops, bytes) one training step's grouped matmuls need, forward
    and backward, over ``layers`` expert layers whose held experts got
    ``rows`` rows in all (the counter's sum over layers), on one chip.

    Operations: ``expert_forward_macs_per_row``, two operations a
    multiply-add, backward twice the forward (each product has two
    gradients), nothing recomputed, and nothing for a row whose expert
    lives elsewhere: the count follows the rows routed, so a grouped
    matmul that computed ``rows x held`` would read ``held`` times under
    its share.  Bytes: forward reads a row (``hidden``) and writes its
    output (``hidden``) once and reads each held expert's three matrices
    once; backward reads the row and its output's gradient, writes the
    row's gradient, reads the matrices again and writes their gradients
    once.  The ``2 width``-wide intermediates stay on the chip in the
    best case and are left out."""
    flops = 3 * 2 * expert_forward_macs_per_row(hidden, width) * rows
    row = hidden * dtype_bytes
    matrices = layers * held * 3 * hidden * width * dtype_bytes
    return flops, (2 + 3) * row * rows + 3 * matrices

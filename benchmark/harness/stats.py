"""The percentile the benchmark reports, in one place."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """``q`` in [0, 100]; linear interpolation between order statistics
    (numpy's default), on plain lists so the served cell's parent never
    needs more than the standard library for its arithmetic."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)

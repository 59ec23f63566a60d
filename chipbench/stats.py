"""The arithmetic of the benchmark's own metrics."""

from __future__ import annotations

import math


def percentile(values: list, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least p% of
    the sample at or below it."""
    if not values:
        raise ValueError("percentile of nothing")
    s = sorted(values)
    return s[max(0, math.ceil(p / 100.0 * len(s)) - 1)]

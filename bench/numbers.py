"""Order statistics the metrics share."""
from __future__ import annotations

import math


def nearest_rank(values, p: float) -> float | None:
    """The nearest-rank `p`th percentile of all `values`: the smallest
    value with at least p% of the values at or below it."""
    xs = sorted(values)
    if not xs:
        return None
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[k - 1]

"""Small arithmetic shared by the metric readers."""

from __future__ import annotations

import math


def nearest_rank(values: list, pct: float) -> float:
    """The ``pct``-th percentile by nearest rank: the smallest value with
    at least ``pct`` percent of the values at or below it."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = max(1, math.ceil(pct / 100.0 * len(ordered)))
    return ordered[rank - 1]

"""The arithmetic of the end-to-end metrics: a percentile over every request
of the window."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100), linear between the two nearest
    ranks; raises on an empty sample, because a tail of nothing is not 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)

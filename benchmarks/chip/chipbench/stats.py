"""Percentile of step-completion intervals."""
from __future__ import annotations

import math


def percentile(values, q):
    """Nearest-rank percentile: the smallest value with at least q% of the
    values at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]

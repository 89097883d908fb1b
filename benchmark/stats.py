"""Percentiles and spreads, the benchmark's own arithmetic."""

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the two
    nearest order statistics (numpy's default rule). Raises on no samples: a
    metric without samples is left out, never reported as 0."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def spread(values) -> float:
    """Distance between the quartiles over the median: the driver's measure
    of how far runs of the same code disagree."""
    med = median(values)
    return (percentile(values, 75.0) - percentile(values, 25.0)) / med

"""Window statistics: percentiles, weighted percentiles, spread."""
from __future__ import annotations

from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics (numpy's default); None for no samples."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    rank = (len(xs) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def weighted_percentile(values: Sequence[float], weights: Sequence[float],
                        q: float) -> Optional[float]:
    """The smallest value whose cumulative weight reaches ``q`` percent
    of the total weight: the percentile of a sample in which each value
    stands ``weight`` times.  Entries of weight 0 are left out."""
    pairs = sorted((float(v), float(w)) for v, w in zip(values, weights)
                   if w > 0)
    total = sum(w for _, w in pairs)
    if not pairs or total <= 0:
        return None
    need = total * q / 100.0
    acc = 0.0
    for v, w in pairs:
        acc += w
        if acc >= need:
            return v
    return pairs[-1][0]


def mean(values: Sequence[float]) -> Optional[float]:
    xs = [float(v) for v in values]
    return sum(xs) / len(xs) if xs else None


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median: the driver's
    measure of how far the runs of one cell disagree."""
    med = percentile(values, 50)
    if med in (None, 0.0):
        return None
    return (percentile(values, 75) - percentile(values, 25)) / abs(med)

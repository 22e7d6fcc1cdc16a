"""Percentile and window arithmetic, kept apart so that tests can check
it by hand-worked numbers."""
from __future__ import annotations

import math


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values):
    return percentile(values, 50)


def summary(values, qs=(50, 75, 90, 95, 99)):
    """Count, mean and a few percentiles, for the line a run prints beside
    its metrics."""
    if not values:
        return None
    out = {"n": len(values), "mean": sum(values) / len(values)}
    out.update({f"p{q}": percentile(values, q) for q in qs})
    return out


def gaps_ending_in(stamps, t0, t1):
    """Gaps between consecutive stamps whose later stamp is in [t0, t1)."""
    return [b - a for a, b in zip(stamps, stamps[1:]) if t0 <= b < t1]


def iqr_share(values):
    """(Q3 - Q1) / median with `statistics.quantiles(n=4)`: the spread the
    contract's bounds are set from."""
    import statistics

    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

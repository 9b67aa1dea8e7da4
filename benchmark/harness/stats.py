"""Medians, tails and spreads, one definition each."""
from __future__ import annotations

import math
import statistics


def quantile(values, q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between order
    statistics (numpy's default)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("quantile of no samples")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return quantile(values, 0.5)


def supported_tail(n: int, ladder=(50, 90, 95, 99, 99.9)) -> float:
    """The highest percentile of `ladder` that has at least ten of `n`
    samples beyond it."""
    ok = [p for p in ladder if round(n * (100 - p), 6) >= 1000]
    if not ok:
        raise ValueError(f"{n} samples support no tail: the median itself "
                         f"needs 20")
    return max(ok)


def tail_or_none(values, want: float = 95):
    """The `want`-th percentile, or None where fewer than ten samples lie
    beyond it: the metric is then left out of the line, so a p95 of forty
    requests is never reported."""
    if len(values) < 20 or supported_tail(len(values)) < want:
        return None
    return quantile(values, want / 100.0)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as `statistics.quantiles(values, n=4)` gives them
    (the driver's rule for a bound)."""
    q1, _, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / abs(statistics.median(values))


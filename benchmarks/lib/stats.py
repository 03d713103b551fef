"""Exact order statistics over the samples a run took (no histogram
buckets: ``obs.Histogram``'s log buckets are up to 26% wide)."""

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least
    ``q`` of all samples at or below it.  The p95 of 200 samples is the
    190th smallest, with 10 beyond it."""
    if not 0.0 < q <= 1.0:
        raise ValueError(f"q must be in (0, 1], got {q}")
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of no samples")
    return ordered[max(math.ceil(q * len(ordered)) - 1, 0)]


def median(values) -> float:
    return statistics.median(values)


def iqr_share(values) -> float:
    """The distance between the first and the third quartile
    (``statistics.quantiles(values, n=4)``) as a share of the median:
    the spread the bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

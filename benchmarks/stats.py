"""Percentiles and spreads, as the benchmark reports them."""

from __future__ import annotations

import math
import statistics

# a percentile is supported when at least this many samples lie beyond it
SAMPLES_BEYOND = 10


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics (numpy's default). Raises on an empty sample."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def samples_needed(q: float) -> int:
    """The least sample count at which `SAMPLES_BEYOND` samples lie beyond
    the q-th percentile: 200 for the 95th, 20 for the median."""
    tail = min(q, 100.0 - q) / 100.0
    if tail <= 0:
        raise ValueError(f"no sample supports the {q}th percentile")
    return math.ceil(round(SAMPLES_BEYOND / tail, 6))


def supported(n: int, q: float) -> bool:
    return n >= samples_needed(q)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median — `statistics.quantiles(values, n=4)`, the driver's measure."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

"""Percentile, median and spread arithmetic of the benchmark.

Kept here so that every PR computes a tail the same way. A request that
never produced the sample (failed, shed, unfinished at the drain cap)
enters at ``cap``, the largest finite value a request can have, so it
can only raise a tail.
"""
from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default). Raises on an empty sample: a metric
    with nothing to read is left out, never reported as 0."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if any(math.isnan(x) for x in xs):
        raise ValueError("percentile of a sample that holds NaN")
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * (q / 100.0)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def with_missing(samples: Iterable[Optional[float]], cap: float) -> List[float]:
    """Replace every missing sample (``None`` or non-finite) by ``cap``."""
    out = []
    for s in samples:
        if s is None or not math.isfinite(s):
            out.append(float(cap))
        else:
            out.append(min(float(s), float(cap)))
    return out


def median(values: Sequence[float]) -> float:
    return float(statistics.median(values))


def iqr_share(values: Sequence[float]) -> float:
    """Distance between the first and third quartile
    (``statistics.quantiles(values, n=4)``) as a share of the median:
    the spread the bounds in BENCHMARK.json are set from."""
    q = statistics.quantiles([float(v) for v in values], n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / abs(med) if med else float("inf")

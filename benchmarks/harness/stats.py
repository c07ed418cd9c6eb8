"""The statistics of a window, in plain Python so that a test can check
them by hand."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile (0-100) by linear interpolation between the
    two nearest ranks, as numpy's default; None for no sample."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def gaps(stamps: Sequence[float]) -> List[float]:
    """Gaps between consecutive stamps of one request."""
    return [b - a for a, b in zip(stamps, stamps[1:])]


def all_gaps(requests: Iterable[Sequence[float]]) -> List[float]:
    """Every gap of every request, all requests together."""
    out: List[float] = []
    for stamps in requests:
        out.extend(gaps(stamps))
    return out


def count_in(stamps: Iterable[float], start: float, end: float) -> int:
    """Stamps in the half-open window [start, end)."""
    return sum(1 for t in stamps if start <= t < end)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median — the measure the bounds are set from."""
    import statistics
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

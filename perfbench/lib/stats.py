"""Percentile, window and spread arithmetic of the benchmark.

Pure Python on lists of floats: nothing here knows about pods or chips,
so the tests can hold every function to numbers worked out by hand.
"""

from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule: the smallest
    value with at least q% of the sample at or below it.  A rank is a
    sample that was really seen, which is what a tail over requests
    should be (no interpolation between a bound pod and a failed one)."""
    if not values:
        raise ValueError("percentile of an empty sample")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside 0..100")
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def in_window(stamps: Iterable[float], t0: float, seconds: float) -> int:
    """How many stamps fall in [t0, t0 + seconds)."""
    t1 = t0 + seconds
    return sum(1 for t in stamps if t0 <= t < t1)


def iqr_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles as ``statistics.quantiles(values, n=4)``
    gives them: the spread the bounds in BENCHMARK.json are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else float("inf")


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    """The union of [start, end) intervals as disjoint sorted intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out

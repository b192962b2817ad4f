"""The arithmetic every metric goes through: percentiles and unions."""

from __future__ import annotations

import math
from typing import Iterable, List, Sequence, Tuple


def percentile(values: Sequence[float], p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default):
    p in [0, 100] over the sorted values."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def merge_intervals(intervals: Iterable[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_length(intervals: Iterable[Tuple[float, float]],
                 clip: Tuple[float, float] = (-math.inf, math.inf)) -> float:
    """Total length covered by the intervals inside ``clip``."""
    lo, hi = clip
    return sum(e - s for s, e in merge_intervals(
        (max(s, lo), min(e, hi)) for s, e in intervals))


def gaps(intervals: Iterable[Tuple[float, float]],
         window: Tuple[float, float]) -> List[Tuple[float, float]]:
    """The uncovered stretches of ``window``."""
    lo, hi = window
    out, cursor = [], lo
    for s, e in merge_intervals((max(s, lo), min(e, hi))
                                for s, e in intervals):
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        out.append((cursor, hi))
    return out

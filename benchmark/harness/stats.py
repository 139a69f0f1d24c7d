"""Percentile and rate arithmetic, in plain Python so a test can hand-check it."""
from __future__ import annotations

from typing import Iterable, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (numpy's default rule), q in [0, 100].
    None for an empty sample: a metric with nothing to read is left out."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    rank = (len(xs) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))


def rate(count: float, seconds: float) -> Optional[float]:
    return None if seconds <= 0 else count / seconds


def gaps(stamps: Sequence[float]) -> list:
    return [b - a for a, b in zip(stamps, stamps[1:])]


def union_seconds(intervals: Iterable[tuple]) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total

"""Percentile and rate arithmetic of the end-to-end metrics.

All the work over all the time, and the tail of all requests: no trimming,
no median of chunks. A stall inside the window therefore moves the rate and
the p95 (`tests/test_arith.py` holds a hand-made sample that shows it).
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank percentile, q in (0, 100]: the smallest value with at
    least q % of the sample at or below it. None for an empty sample."""
    v: List[float] = sorted(values)
    if not v:
        return None
    return v[min(len(v) - 1, max(0, math.ceil(q / 100.0 * len(v)) - 1))]


def rate(stamps: Iterable[float], t0: float, t1: float,
         weights: Optional[Iterable[float]] = None) -> float:
    """Work stamped inside [t0, t1) over the window's whole length."""
    if weights is None:
        n = sum(1 for t in stamps if t0 <= t < t1)
    else:
        n = sum(w for t, w in zip(stamps, weights) if t0 <= t < t1)
    return n / (t1 - t0)


def median(values: Iterable[float]) -> Optional[float]:
    return percentile(values, 50.0)

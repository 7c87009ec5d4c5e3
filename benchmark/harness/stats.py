"""Percentiles, spreads and due-time arithmetic: the benchmark's own
arithmetic, kept here so that no later PR can change how a number is
reduced."""
from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics, or None of nothing."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Iterable[float]) -> Optional[float]:
    xs = [float(v) for v in values]
    return statistics.median(xs) if xs else None


def highest_supported_percentile(n: int) -> float:
    """The highest of 50/90/95/99 that has at least ten samples beyond
    it among ``n`` (choosing-metrics section 1)."""
    best = 50.0
    for q in (90.0, 95.0, 99.0):
        if n * (100.0 - q) / 100.0 >= 10.0:
            best = q
    return best


def iqr_share(values: Sequence[float]) -> Optional[float]:
    """Distance between first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` — the spread the
    bounds are set from."""
    xs = [float(v) for v in values]
    if len(xs) < 2:
        return None
    q1, _, q3 = statistics.quantiles(xs, n=4)
    med = statistics.median(xs)
    return (q3 - q1) / med if med else None


def ttft_ms(due_t: float, first_token_t: float) -> float:
    """Time to first token from when the request was DUE, not from when
    the generator got round to sending it: a stall of the loop counts
    against the requests it delayed."""
    return (first_token_t - due_t) * 1e3


def gaps_ms(token_times: Sequence[float], lo: float, hi: float) -> List[float]:
    """Gaps between consecutive output tokens of one request whose later
    token landed inside ``[lo, hi)``."""
    out = []
    for a, b in zip(token_times, token_times[1:]):
        if lo <= b < hi:
            out.append((b - a) * 1e3)
    return out

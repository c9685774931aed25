"""Percentiles, spreads and window arithmetic used by the readers."""
from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks; +inf values (failed requests) sort last."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    if math.isinf(vals[hi]):
        return math.inf if pos > lo or math.isinf(vals[lo]) else vals[lo]
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def spread(values):
    """(third quartile - first quartile) / median, with the quartiles of
    ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def in_window(times, t0, t1):
    """How many of ``times`` lie in [t0, t1]."""
    return sum(1 for t in times if t0 <= t <= t1)


def union_length(intervals, lo=-math.inf, hi=math.inf):
    """Length of the union of (start, end) intervals clipped to [lo, hi]."""
    total, reach = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, reach), min(e, hi)
        if e > s:
            total += e - s
            reach = e
    return total


def gaps(intervals, lo, hi):
    """The idle (start, end) gaps inside [lo, hi] not covered by any of
    ``intervals``."""
    out, reach = [], lo
    for s, e in sorted(intervals):
        if e <= reach:
            continue
        if s > reach:
            out.append((reach, min(s, hi)))
        reach = max(reach, e)
        if reach >= hi:
            break
    if reach < hi:
        out.append((reach, hi))
    return [(s, e) for s, e in out if e > s]

"""Percentiles and pooled gaps: the arithmetic behind the latency metrics."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default), of a non-empty sequence."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def pooled_gaps(token_times_by_request, t_open: float, t_close: float):
    """Gaps between consecutive streamed tokens of each request, pooled
    over all requests; a gap counts where it ENDS inside [t_open, t_close)
    (by the arrival of its later token)."""
    gaps = []
    for times in token_times_by_request:
        for a, b in zip(times, times[1:]):
            if t_open <= b < t_close:
                gaps.append(b - a)
    return gaps


def tokens_in_window(token_times_by_request, t_open: float,
                     t_close: float) -> int:
    """Output tokens that reached the clients inside the window, by arrival
    time: a request that straddles an edge counts only its part."""
    return sum(1 for times in token_times_by_request for t in times
               if t_open <= t < t_close)


def merge(intervals):
    """Sorted disjoint union of half-open intervals."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def intersect(xs, ys):
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys):
    """xs minus ys, both sorted disjoint interval lists."""
    out = []
    j = 0
    for a, b in xs:
        cur = a
        while j < len(ys) and ys[j][1] <= cur:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out

"""Interval arithmetic on (start, end) pairs, in any one unit of time."""

from __future__ import annotations


def union(intervals):
    """Sorted, disjoint intervals that cover the same points."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def subtract(a, b):
    """The part of union(a) that union(b) does not cover."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(intervals, lo, hi):
    """The parts of [lo, hi] that union(intervals) leaves uncovered."""
    return subtract([(lo, hi)], intervals)


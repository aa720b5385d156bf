"""Interval arithmetic over a trace: busy union, idle gaps, overlap.

An interval is a ``(start, end)`` pair of seconds on one clock. Every
function takes any iterable of intervals and returns a sorted list of
disjoint ones (or a length), so the reduction from a trace to a metric
is the same code whatever produced the events.
"""

from __future__ import annotations


def union(intervals) -> list[tuple[float, float]]:
    """Sorted disjoint union; empty intervals are dropped."""
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``intervals`` cut to ``[lo, hi]``."""
    return [(max(s, lo), min(e, hi)) for s, e in union(intervals)
            if e > lo and s < hi]


def subtract(a, b) -> list[tuple[float, float]]:
    """The parts of ``a`` that no interval of ``b`` covers."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` between busy ones."""
    return subtract([(lo, hi)], busy)

"""Exact rate and interval arithmetic over call records and trace events."""
from __future__ import annotations


def rate(count: int, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return count / seconds


def union_length(intervals) -> float:
    """Total length covered by a set of ``(start, end)`` intervals."""
    return sum(e - s for s, e in merge(intervals))


def merge(intervals) -> list:
    """``intervals`` merged into disjoint, sorted ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]

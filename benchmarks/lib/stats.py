"""Percentile, self-time and interval arithmetic: the benchmark's own, so
that no PR to the program can move a metric by changing how it is computed."""

from __future__ import annotations

import math


def percentile(values: list[float], p: float) -> float | None:
    """Nearest rank: the smallest value with at least p of the samples at or
    below it. None for no samples."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(p * len(s)) - 1)]


def supported_percentile(n: int) -> float:
    """The highest percentile with at least ten samples beyond it (the
    guide's rule), as a fraction; 0.5 when even the median has fewer."""
    for p in (0.999, 0.99, 0.95, 0.9, 0.75):
        if round(n * (1 - p), 9) >= 10:
            return p
    return 0.5


def union_length(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by (start, end) intervals, overlaps once."""
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


def self_times(spans: list[dict]) -> dict[str, float]:
    """span id -> self time: the span's duration minus the part of it that
    its child spans cover. A span is {"id", "parent", "start", "end"}."""
    kids: dict[str, list] = {}
    for sp in spans:
        kids.setdefault(sp["parent"], []).append(sp)
    out = {}
    for sp in spans:
        covered = union_length([
            (max(k["start"], sp["start"]), min(k["end"], sp["end"]))
            for k in kids.get(sp["id"], [])
            if min(k["end"], sp["end"]) > max(k["start"], sp["start"])])
        out[sp["id"]] = (sp["end"] - sp["start"]) - covered
    return out

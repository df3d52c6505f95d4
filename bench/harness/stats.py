"""Metric arithmetic on plain lists. No metric is built from medians of
pieces: a rate is all the work over all the time, a tail is the tail of all
requests, failures counted in."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (q in [0, 100]) of a non-empty list."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def latency_table(done_s, due_s, ok, over_limit_ms: float) -> list:
    """Latency in ms of every request, from the moment it was DUE to the
    last byte of its reply. A request that failed or was shed is counted as
    ``over_limit_ms`` (over any limit), so it sits in the tail."""
    out = []
    for d, u, good in zip(done_s, due_s, ok):
        out.append((d - u) * 1e3 if good else float(over_limit_ms))
    return out


def open_loop_summary(due_s, sent_s, done_s, ok, seconds: float) -> dict:
    """All requests of an open-loop window -> the quantities a mix may
    report. ``seconds`` is the window; a failure reads as the whole window."""
    lat = latency_table(done_s, due_s, ok, seconds * 1e3)
    late = [(s - u) * 1e3 for s, u in zip(sent_s, due_s)]
    n = len(lat)
    return {"attempted": n, "failed": n - sum(1 for g in ok if g),
            "p95_ms": percentile(lat, 95), "p50_ms": percentile(lat, 50),
            "late_p99_ms": percentile(late, 99),
            "completed_per_s": sum(1 for g in ok if g) / seconds}


def spread(values) -> float:
    """Interquartile distance as a share of the median — the measure the
    bounds are set from (statistics.quantiles, n=4)."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)

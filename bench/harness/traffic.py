"""The one general traffic generator. A mix is a data file of parameters;
this module turns (mix, seed, seconds) into frame sizes and a send schedule.

Every seed gets the SAME set of sizes and the SAME set of gaps between
arrivals, in another order: sizes are the quantiles of the mix's size classes
and gaps are the quantiles of the exponential law at the mix's rate (so the
arrivals look Poisson, burst and lull included, but the amount of work in a
window does not move with the seed). Only the order, and the rows' values,
come from the seed.
"""

from __future__ import annotations

import math

import numpy as np

from bench.harness.data import host_rng


def pool_sizes(pool: dict, seed: int) -> list:
    """Row counts of a pool of scoring frames.

    pool = {"count": 256, "classes": [{"share": 0.7, "lo": 1, "hi": 1}, ...]}
    Each class takes its share of the count; inside a class sizes are the
    log-uniform quantiles of [lo, hi]. The seed only shuffles."""
    count = int(pool["count"])
    classes = pool["classes"]
    sizes = []
    left = count
    for i, c in enumerate(classes):
        k = left if i == len(classes) - 1 else int(round(count * c["share"]))
        k = min(k, left)
        left -= k
        lo, hi = float(c["lo"]), float(c["hi"])
        for j in range(k):
            q = (j + 0.5) / k
            sizes.append(int(round(math.exp(math.log(lo) + q *
                                            (math.log(hi) - math.log(lo))))))
    order = host_rng(seed, stream=11).permutation(len(sizes))
    return [sizes[i] for i in order]


def _warp(u: np.ndarray, burst: dict, seconds: float) -> np.ndarray:
    """Map unit-intensity times onto on/off bursts: ``factor`` times the
    mean rate for ``on_s``, then a lull for ``off_s`` that keeps the mean."""
    on, off, k = float(burst["on_s"]), float(burst["off_s"]), \
        float(burst["factor"])
    period = on + off
    if k * on > period:
        raise ValueError("burst factor * on_s exceeds the period")
    low = (period - k * on) / off if off > 0 else 0.0
    # cumulative intensity over one period, piecewise linear
    t = np.asarray(u, np.float64)
    whole = np.floor(t / period)
    r = t - whole * period                    # intensity-time inside a period
    inside = np.where(r <= k * on, r / k,
                      on + (r - k * on) / max(low, 1e-12))
    return np.minimum(whole * period + inside, seconds)


def open_loop_schedule(mix: dict, seconds: float, n_frames: int,
                       seed: int):
    """-> (due seconds from window start, frame index), both (n,).
    n = rate_rps * seconds requests, every frame used equally often."""
    rate = float(mix["rate_rps"])
    n = max(int(round(rate * seconds)), 1)
    rng = host_rng(seed, stream=12)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()              # the set spans the window
    gaps = gaps[rng.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    if mix.get("burst"):
        due = _warp(due, mix["burst"], seconds)
    frames = (np.arange(n) % n_frames)[rng.permutation(n)]
    return due, frames

"""RollupStats — lazy fused per-column statistics.

Reference: water/fvec/RollupStats.java:30 — per-Vec min/max/mean/sigma/
naCnt/nzCnt + histogram computed by a dedicated MRTask, stored under a hidden
key, invalidated on write.

TPU-native: a single fused jitted masked reduction over the row-sharded
array; XLA emits one pass over HBM and one psum. Cached on the immutable
Column object (no invalidation protocol needed — copy-on-write columns)."""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Rollups:
    min: float
    max: float
    mean: float
    sigma: float
    na_count: int
    nz_count: int
    rows: int  # valid (non-NA) rows


@functools.lru_cache(maxsize=8)
def _rollup_fn(is_cat: bool):
    @jax.jit
    def roll(data):
        if is_cat:
            valid = data >= 0
            x = jnp.where(valid, data, 0).astype(jnp.float32)
        else:
            valid = ~jnp.isnan(data)
            x = jnp.where(valid, data, 0.0)
        n = jnp.sum(valid)
        s = jnp.sum(x, dtype=jnp.float32)
        ss = jnp.sum(x * x, dtype=jnp.float32)
        mn = jnp.min(jnp.where(valid, x, jnp.inf))
        mx = jnp.max(jnp.where(valid, x, -jnp.inf))
        nz = jnp.sum(valid & (x != 0))
        return n, s, ss, mn, mx, nz

    return roll


def compute_rollups(col) -> Rollups:
    if col.data is None:  # string column: host-side
        a = col.host_data[: col.nrows]
        na = sum(1 for v in a if v is None)
        return Rollups(np.nan, np.nan, np.nan, np.nan, na, len(a) - na, len(a) - na)
    n, s, ss, mn, mx, nz = _rollup_fn(col.is_categorical)(col.data)
    n = int(n)
    # padding rows are NA-encoded, so they are already excluded; true NA count:
    na = col.padded_rows - n - (col.padded_rows - col.nrows)
    mean = float(s) / n if n else float("nan")
    var = max(float(ss) / n - mean * mean, 0.0) if n else float("nan")
    sigma = float(np.sqrt(var * n / (n - 1))) if n and n > 1 else 0.0
    return Rollups(float(mn) if n else float("nan"),
                   float(mx) if n else float("nan"),
                   mean, sigma, int(na), int(nz), n)


MODE_SELECT_LEVELS = 1024   # up to here a level's count is a compare-and-sum


@functools.lru_cache(maxsize=32)
def _mode_fn(k: int):
    @jax.jit
    def mode(codes):
        codes = codes.astype(jnp.int32)
        if k <= MODE_SELECT_LEVELS:
            # levels on sublanes, rows on lanes: no scatter over the rows
            counts = jnp.sum(codes[None, :] == jnp.arange(k)[:, None],
                             axis=1, dtype=jnp.int32)
        else:
            counts = jnp.zeros(k, jnp.int32).at[jnp.maximum(codes, 0)].add(
                (codes >= 0).astype(jnp.int32))
        return jnp.argmax(counts)

    return mode


def compute_mode(col) -> int:
    """Most frequent level of a categorical column (the first of equals;
    NA and pad rows, code -1, count for none)."""
    return int(_mode_fn(max(col.cardinality, 1))(col.data))


@functools.lru_cache(maxsize=8)
def _hist_fn(nbins: int):
    @jax.jit
    def hist(data, lo, hi):
        valid = ~jnp.isnan(data)
        x = jnp.where(valid, data, lo)
        w = jnp.where(valid, 1.0, 0.0)
        idx = jnp.clip(((x - lo) / jnp.maximum(hi - lo, 1e-30) * nbins).astype(jnp.int32), 0, nbins - 1)
        return jnp.zeros(nbins, jnp.float32).at[idx].add(w)

    return hist


def histogram(col, nbins: int = 20) -> np.ndarray:
    """Per-column histogram (RollupStats histogram part)."""
    r = col.rollups
    h = _hist_fn(nbins)(col.data, jnp.float32(r.min), jnp.float32(r.max))
    return np.asarray(h)

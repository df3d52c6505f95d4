"""The data recipe of the airline on-time deployment (szilard/GBM-perf):
everything a run feeds the system comes from ``--seed``.

Eight predictors in the source's order and types and a binary response:

  Month (12 levels), DayofMonth (31), DayOfWeek (7), UniqueCarrier (22),
  Origin (300), Dest (300) as level codes; DepTime, Distance as float32;
  dep_delayed_15min 0/1, about 19% positive.

What the seed moves is the rows. What never moves (drawn once from
``LAW_SEED``, so every seed is the same learning problem on fresh rows):
the level probabilities (uniform for the three calendar columns, a Zipf law
of exponent 1 over carriers and over airports, laid on the level codes in a
fixed shuffled order so that a level's code says nothing of its frequency:
the ten busiest of the 300 airports carry 46.6% of the rows), the laws of
``DepTime`` (500 + 1800 * (u**0.7 + v) / 2, u, v uniform: most departures
in the day, a thin early tail) and ``Distance`` (exp(6.4 + 0.7 z) clipped
to [30, 5000]), and the response model: one logistic model with an effect a
level of every enum column, a smooth effect of ``DepTime`` (delays build up
through the day), a small one of ``Distance``, and a carrier x origin
interaction of rank two (so that trees have reason to go deep), with the
intercept set for about 19% positives. No value is missing, as in the
source's file. A variant with ``na_share`` of the rows of ``Origin`` and of
``DepTime`` missing (code -1, NaN), independently of everything else, is
what the controls and tests use to see a fault in the missing bin of a
histogram or in the side a split gives it; no cell runs it.

Everything is made on the device in ONE jitted call. A level's effect is
looked up by compare-and-select over the level axis, not by a per-row
gather. Nothing here imports the program: the reference reuses these arrays
as its own inputs.
"""

from __future__ import annotations

import functools

import numpy as np

from bench.harness.data import fold_seed, seed_key  # noqa: F401 (re-export)

LAW_SEED = 28
RESPONSE_NAME = "y"                  # bench.harness.phases.job_body posts it
RESPONSE_DOMAIN = ("N", "Y")
INTERCEPT = -1.75                    # 19.1% positives (see positives())

# name, type, levels — the source's columns in the source's order
COLUMNS = (("Month", "enum", 12), ("DayofMonth", "enum", 31),
           ("DayOfWeek", "enum", 7), ("UniqueCarrier", "enum", 22),
           ("Origin", "enum", 300), ("Dest", "enum", 300),
           ("DepTime", "real", 0), ("Distance", "real", 0))
ZIPF = ("UniqueCarrier", "Origin", "Dest")     # the others are uniform
EFFECT_SCALE = {"Month": 0.25, "DayofMonth": 0.08, "DayOfWeek": 0.15,
                "UniqueCarrier": 0.35, "Origin": 0.45, "Dest": 0.3}
INTERACTION_SCALE = 0.6
NA_SHARE = 0.0                      # the cell's frame: nothing is missing
NA_COLUMNS = ("Origin", "DepTime")  # where the variant lays its missing rows


def code_dtype(levels: int):
    """Narrowest signed integer that holds the codes and the -1 NA."""
    return np.int8 if levels <= 126 else np.int16


def frame_columns() -> list:
    """(name, type, domain) per column for whoever installs the frame: an
    enum column's level names as plain strings in code order, None for a
    numeric one."""
    return [(name, ctype, [f"{name}_{i:03d}" for i in range(levels)]
             if ctype == "enum" else None) for name, ctype, levels in COLUMNS]


@functools.lru_cache(maxsize=1)
def laws() -> dict:
    """The fixed part: per enum column the level probabilities ``p`` and the
    level effects ``effect``; the two rank-one factors of the carrier x
    origin interaction."""
    rng = np.random.default_rng(LAW_SEED)
    out = {"p": {}, "effect": {}}
    for name, ctype, levels in COLUMNS:
        if ctype != "enum":
            continue
        if name in ZIPF:
            p = 1.0 / np.arange(1, levels + 1)
            p = (p / p.sum())[rng.permutation(levels)]
        else:
            p = np.full(levels, 1.0 / levels)
        out["p"][name] = p.astype(np.float64)
        out["effect"][name] = (EFFECT_SCALE[name]
                               * rng.standard_normal(levels)
                               ).astype(np.float32)
    out["inter"] = [(rng.standard_normal(22).astype(np.float32),
                     rng.standard_normal(300).astype(np.float32))
                    for _ in range(2)]
    return out


def top_share(name: str, k: int = 10) -> float:
    """Share of the rows the k most frequent levels of a column carry."""
    return float(np.sort(laws()["p"][name])[::-1][:k].sum())


def _lookup(table, code):
    """table[code] for every row, by compare-and-select over the levels."""
    import jax.numpy as jnp

    hit = jnp.arange(table.shape[0], dtype=jnp.int32)[None, :] \
        == code[:, None].astype(jnp.int32)
    return jnp.sum(jnp.where(hit, jnp.asarray(table)[None, :], 0.0), axis=1)


def _draw(key, p, n):
    """n level codes with probabilities p (inverse CDF; int32)."""
    import jax
    import jax.numpy as jnp

    cdf = np.cumsum(p)[:-1].astype(np.float32)
    u = jax.random.uniform(key, (n,), jnp.float32)
    return jnp.sum(u[:, None] >= jnp.asarray(cdf)[None, :], axis=1,
                   dtype=jnp.int32)


@functools.lru_cache(maxsize=8)
def _device_columns_fn(n: int, with_response: bool, sharding,
                       na_share: float):
    import jax
    import jax.numpy as jnp

    law = laws()

    def make(key):
        cols, codes = [], {}
        logit = jnp.full(n, INTERCEPT, jnp.float32)
        for i, (name, ctype, levels) in enumerate(COLUMNS):
            k = jax.random.fold_in(key, i)
            if ctype == "enum":
                c = _draw(k, law["p"][name], n)
                codes[name] = c
                logit = logit + _lookup(law["effect"][name], c)
                cols.append(c.astype(code_dtype(levels)))
            elif name == "DepTime":
                u = jax.random.uniform(k, (2, n), jnp.float32)
                x = 500.0 + 1800.0 * (u[0] ** 0.7 + u[1]) / 2.0
                logit = logit + 1.2 * ((x - 500.0) / 1800.0) ** 2 - 0.4
                cols.append(x)
            else:
                z = jax.random.normal(k, (n,), jnp.float32)
                x = jnp.clip(jnp.exp(6.4 + 0.7 * z), 30.0, 5000.0)
                logit = logit + 0.1 * (jnp.log(x) - 6.4)
                cols.append(x)
        for a, b in law["inter"]:
            logit = logit + INTERACTION_SCALE \
                * _lookup(a, codes["UniqueCarrier"]) \
                * _lookup(b, codes["Origin"])
        # the variant's missing values, laid on after the response was decided
        for i, (name, ctype, _levels) in enumerate(COLUMNS):
            if na_share > 0 and name in NA_COLUMNS:
                gone = jax.random.uniform(jax.random.fold_in(key, 2000 + i),
                                          (n,), jnp.float32) < na_share
                cols[i] = jnp.where(gone, -1 if ctype == "enum" else jnp.nan,
                                    cols[i]).astype(cols[i].dtype)
        if not with_response:
            return tuple(cols)
        u = jax.random.uniform(jax.random.fold_in(key, 1000), (n,),
                               jnp.float32)
        y = (u < jax.nn.sigmoid(logit)).astype(jnp.int8)
        return tuple(cols) + (y,)

    n_out = len(COLUMNS) + (1 if with_response else 0)
    return jax.jit(make, out_shardings=(sharding,) * n_out
                   if sharding is not None else None)


def device_columns(seed: int, n: int, *, stream: int = 0,
                   with_response: bool = True, sharding=None,
                   na_share: float = None) -> tuple:
    """The eight device columns of n rows (enum columns as level codes in
    their narrowest integer, the two numeric ones float32) + an int8 0/1
    response, made in ONE jitted call from (seed, stream). ``na_share``
    (default ``NA_SHARE``, nothing) is the variant's share of missing rows
    in each of ``NA_COLUMNS``."""
    share = float(NA_SHARE if na_share is None else na_share)
    fn = _device_columns_fn(int(n), bool(with_response), sharding, share)
    return fn(seed_key(seed, stream))


def positives(seed: int = 1, n: int = 200_000) -> float:
    """Share of positive responses the recipe gives (a check of
    INTERCEPT)."""
    return float(np.mean(np.asarray(device_columns(seed, n)[-1])))

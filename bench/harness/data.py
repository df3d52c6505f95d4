"""The data recipe: everything a run feeds the system comes from ``--seed``.

HIGGS width: 28 standard-normal float32 feature columns and a binary response
drawn from one fixed logistic model of them (the coefficients never move with
the seed, so every seed is the same learning problem on fresh rows). Training
frames and the large scoring frames are made on the device in one jitted call;
the small scoring frames are made on the host (a few thousand rows in all).

Nothing here imports the program: the reference regenerates or reuses these
arrays as its own inputs.
"""

from __future__ import annotations

import functools

import numpy as np

N_FEATURES = 28
COEF_SEED = 21
FEATURE_NAMES = tuple(f"x{i}" for i in range(N_FEATURES))
RESPONSE_NAME = "y"
RESPONSE_DOMAIN = ("N", "Y")


def coefficients() -> np.ndarray:
    """The fixed logistic model behind the response (28 float32 in (-1, 1))."""
    return np.random.default_rng(COEF_SEED).uniform(
        -1.0, 1.0, N_FEATURES).astype(np.float32)


def fold_seed(seed: int) -> tuple:
    """``--seed`` may pass 2**31; split it into two words that each fit."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return seed % (2 ** 31 - 1), seed // (2 ** 31 - 1)


def seed_key(seed: int, stream: int = 0):
    """A jax PRNG key for (seed, stream); streams keep frames apart."""
    import jax

    lo, hi = fold_seed(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.random.fold_in(key, int(stream))


def host_rng(seed: int, stream: int = 0) -> np.random.Generator:
    lo, hi = fold_seed(seed)
    return np.random.default_rng([lo, hi, int(stream)])


@functools.lru_cache(maxsize=8)
def _device_columns_fn(n: int, with_response: bool, sharding):
    import jax
    import jax.numpy as jnp

    coef = coefficients()

    def make(key):
        cols = []
        logit = jnp.zeros(n, jnp.float32)
        for i in range(N_FEATURES):
            x = jax.random.normal(jax.random.fold_in(key, i), (n,),
                                  jnp.float32)
            logit = logit + coef[i] * x
            cols.append(x)
        if not with_response:
            return tuple(cols)
        u = jax.random.uniform(jax.random.fold_in(key, 1000), (n,),
                               jnp.float32)
        y = (u < jax.nn.sigmoid(logit)).astype(jnp.int8)
        return tuple(cols) + (y,)

    n_out = N_FEATURES + (1 if with_response else 0)
    return jax.jit(make, out_shardings=(sharding,) * n_out
                   if sharding is not None else None)


def device_columns(seed: int, n: int, *, stream: int = 0,
                   with_response: bool = True, sharding=None) -> tuple:
    """28 float32 device columns of n rows (+ an int8 0/1 response), made in
    ONE jitted call from (seed, stream)."""
    fn = _device_columns_fn(int(n), bool(with_response), sharding)
    return fn(seed_key(seed, stream))


def host_features(rng: np.random.Generator, n: int) -> np.ndarray:
    """(n, 28) float32 standard-normal rows for a small scoring frame."""
    return rng.standard_normal((n, N_FEATURES), dtype=np.float32)

"""The data recipe of the MNIST deployment (mnist8m, LIBSVM data sets;
Loosli, Canu and Bottou 2007): everything a run feeds the system comes from
``--seed``.

784 pixel columns ``C1`` .. ``C784`` (row-major over a 28 x 28 image, H2O's
names for a headerless file) holding whole numbers 0-255 as float32, as H2O
parses an int column, and a response ``y`` of 10 levels "0" .. "9".

What the seed moves is the rows. What never moves (drawn once from
``LAW_SEED``, so every seed is the same learning problem on fresh rows):

- ``DEAD`` (67 pixels) is zero in every row, as 67 pixels are zero in every
  image of MNIST's 60,000 training images. Which 67 they are in MNIST is not
  known here: they are the border pixels nearest the four corners.
- a stroke template a class over the 717 live pixels: ``p[c, j]``, the
  chance that pixel j of an image of class c is inked, a logistic of a sum
  of Gaussian blobs (a stem shared by every class and six blobs of the
  class's own), its offset set so that 19% of all pixels are inked, as in
  MNIST.
- the ink law: an inked pixel is 255 with chance ``FULL`` (MNIST's strokes
  are saturated at their core) and uniform over 1-254 otherwise.

A row draws its class c uniformly, a second class c2 uniformly from the
other nine and a blend m uniform on [0, ``BLEND``) (a stroke that leans
towards another digit, never past half way: the classes overlap, and no
rule reads every row's class), then each pixel from one uniform u: inked where u < q, q =
(1 - m) p[c, j] + m p[c2, j], and its intensity from u / q, which is
uniform on [0, 1) given the ink.

The columns are made on the device in ``GROUP``-column calls of one jitted
function, so that the generation's peak stays near the frame's own size (a
(rows, 784) matrix split into columns would need twice that). Nothing here
imports the program: the reference reuses these arrays as its own inputs.
"""

from __future__ import annotations

import functools

import numpy as np

from bench.harness.data import fold_seed, seed_key  # noqa: F401 (re-export)

LAW_SEED = 40
SIDE = 28
PIXELS = SIDE * SIDE
CLASSES = 10
N_DEAD = 67
GROUP = 56                           # 784 = 14 x 56
INKED = 0.19                         # share of all pixels inked, as MNIST
FULL = 0.45                          # share of inked pixels at 255
BLOBS = 6
CONTRAST = 3.0                       # the blobs' weight in a template's logit
BLEND = 0.5
RESPONSE_NAME = "y"                  # bench.harness.phases.job_body posts it
RESPONSE_DOMAIN = tuple(str(c) for c in range(CLASSES))
NAMES = tuple(f"C{j + 1}" for j in range(PIXELS))


def frame_columns() -> list:
    """(name, type, domain) per column for whoever installs the frame."""
    return [(name, "int", None) for name in NAMES]


def dead_pixels() -> np.ndarray:
    """The ``N_DEAD`` pixel indices that are zero in every row: of the
    border ring, those nearest a corner (ties by index)."""
    r, c = np.divmod(np.arange(PIXELS), SIDE)
    border = (r == 0) | (c == 0) | (r == SIDE - 1) | (c == SIDE - 1)
    corner = np.minimum(r, SIDE - 1 - r) + np.minimum(c, SIDE - 1 - c)
    order = np.lexsort((np.arange(PIXELS), corner))
    return np.sort(order[border[order]][:N_DEAD])


def _field(rng, centres, widths) -> np.ndarray:
    r, c = np.divmod(np.arange(PIXELS), SIDE)
    out = np.zeros(PIXELS)
    for (cr, cc), w in zip(centres, widths):
        out += np.exp(-((r - cr) ** 2 + (c - cc) ** 2) / (2.0 * w * w))
    return out


@functools.lru_cache(maxsize=1)
def templates() -> np.ndarray:
    """(CLASSES, PIXELS) float32 chance of ink; 0 on the dead pixels."""
    rng = np.random.default_rng(LAW_SEED)
    stem = _field(rng, rng.uniform(9, 19, (2, 2)), rng.uniform(3, 5, 2))
    fields = np.stack([
        0.5 * stem + _field(rng, rng.uniform(5, 23, (BLOBS, 2)),
                            rng.uniform(1.5, 3.5, BLOBS))
        for _ in range(CLASSES)])
    live = np.ones(PIXELS, bool)
    live[dead_pixels()] = False

    def chance(a):
        return np.where(live, 1.0 / (1.0 + np.exp(-(a + CONTRAST * fields))), 0.0)

    lo, hi = -20.0, 5.0                  # the share inked rises with a
    for _ in range(80):
        a = (lo + hi) / 2
        lo, hi = (a, hi) if chance(a).mean() < INKED else (lo, a)
    return chance((lo + hi) / 2).astype(np.float32)


@functools.lru_cache(maxsize=4)
def _group_fn(n: int, sharding):
    """One jitted call: ``GROUP`` pixel columns of n rows from (key, class
    codes, the group's (CLASSES, GROUP) templates, its first index). Pixel
    j's uniforms are ``uniform(fold_in(key, j), (n,))``."""
    import jax
    import jax.numpy as jnp

    def make(key, y, tab, first):
        y = y.astype(jnp.int32)
        other = (y + jax.random.randint(jax.random.fold_in(key, 10_001),
                                        (n,), 1, CLASSES)) % CLASSES
        m = BLEND * jax.random.uniform(jax.random.fold_in(key, 10_002),
                                       (n,), jnp.float32)

        def chance(c):          # (GROUP, n) template of each row's class c
            return sum(jnp.where(c[None, :] == k, tab[k][:, None], 0.0)
                       for k in range(CLASSES))

        q = (1.0 - m) * chance(y) + m * chance(other)
        u = jax.vmap(lambda j: jax.random.uniform(
            jax.random.fold_in(key, j), (n,), jnp.float32))(
                first + jnp.arange(GROUP))
        v = u / jnp.maximum(q, 1e-30)
        ink = jnp.where(v < FULL, 255.0,
                        1.0 + jnp.floor((v - FULL) / (1.0 - FULL) * 254.0))
        X = jnp.where(u < q, ink, 0.0)
        return tuple(X[i] for i in range(GROUP))

    return jax.jit(make, out_shardings=(sharding,) * GROUP
                   if sharding is not None else None)


@functools.lru_cache(maxsize=4)
def _response_fn(n: int, sharding):
    import jax
    import jax.numpy as jnp

    def make(key):
        return jax.random.randint(jax.random.fold_in(key, 10_000), (n,), 0,
                                  CLASSES).astype(jnp.int8)

    return jax.jit(make, out_shardings=sharding)


def device_columns(seed: int, n: int, *, stream: int = 0,
                   sharding=None) -> tuple:
    """The 784 float32 pixel columns of n rows and the int8 class codes,
    from (seed, stream): ``PIXELS // GROUP`` calls of one jitted function
    and one for the classes."""
    import jax.numpy as jnp

    key = seed_key(seed, stream)
    y = _response_fn(int(n), sharding)(key)
    tab = templates()
    fn = _group_fn(int(n), sharding)
    cols = []
    for first in range(0, PIXELS, GROUP):
        cols.extend(fn(key, y, jnp.asarray(tab[:, first:first + GROUP]),
                       jnp.int32(first)))
    return tuple(cols) + (y,)

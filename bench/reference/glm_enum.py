"""Plain reference for the binomial GLM over enum and numeric columns (the
airline deployment of szilard/benchm-ml's linear-models section): IRLS on a
dense design, no penalty. It imports nothing of the program and takes
nothing the program made as an input; what it shares with
``bench/reference/glm.py`` is copied, not imported.

A row, as the configuration's ``columns`` state them:

    x = [onehot_-1(enum 1), ..., onehot_-1(enum k),
         (num 1 - m1) / s1, ..., (num j - mj) / sj, 1]

``onehot_-1`` drops the first level; m, s are the column's mean and standard
deviation (n - 1). 668 + 1 entries for the airline table, nine non-zero.

    repeat: eta = X b; mu = sigmoid(eta); w = mu (1 - mu);
            G = X'WX; g = X'(y - mu); q = G b + g; b <- solve(G, q)
    from b = (0, ..., 0, logit(mean y)) until max|b - b_prev| < eps

``q`` is X'Wz of the working response z = eta + (y - mu) / w, formed on the
host: w z = w eta + (y - mu), and X'W eta = G b holds exactly, so only the
part that does not cancel is summed over the rows in float32.

Straight ``jax.numpy`` in float32 with every product at ``HIGHEST``, row
block by row block; a block's G, g and deviance are summed over the blocks
in float64 on the host, where the system is solved in float64. The reference
runs to ``REF_EPS``, tighter than the configuration's ``beta_epsilon``, so it
stands at the optimum the program's iterates approach (on a small frame the
float32 sums of a block keep the steps of a rare level's coefficient above
``REF_EPS``; it then stops where the steps, already under ``STALL_BELOW``,
no longer shrink).

``fit(..., precision="control")`` is the control, the nearest precision
below the stated one: the operands of every product rounded to bfloat16
(float32 accumulation), as a TPU's default matmul precision would.
``fit(..., fault=...)`` plants the faults a GLM over enum columns can have:
``one_iteration`` (b after the first step), ``half_batch`` (every sum over
the first half of the rows only), ``level_shift`` (the first enum column of
the most levels reads its codes off by one against the dropped first level,
so every coefficient of that column carries its neighbour's name).

No value is missing in the configuration's frame, and the reference does
not impute: a missing code or a NaN is an error in its input.
"""

from __future__ import annotations

import functools

import numpy as np

REF_EPS = 1e-7
REF_MAX_ITER = 100
CONTROL_MAX_ITER = 12       # of a control or a planted fault
STALL_BELOW = 1e-5          # the reference may stall in rounding below this
MAX_BLOCK = 250_000
PRECISIONS = {"reference": None, "control": "bfloat16"}
FAULTS = ("one_iteration", "half_batch", "level_shift")
RANK_BLOCK = 32_768        # ranks of a block of the sorted rows fit int32


def block_rows(n: int) -> int:
    """Largest divisor of n that is at most MAX_BLOCK (blocks tile n)."""
    for b in range(min(n, MAX_BLOCK), 0, -1):
        if n % b == 0:
            return b
    return n


def columns_of(cfg: dict):
    """(enum levels in column order, number of numeric columns); enum
    columns come first, as the configuration lists them."""
    levels = [int(c["levels"]) for c in cfg["columns"] if c["type"] == "enum"]
    n_num = sum(1 for c in cfg["columns"] if c["type"] != "enum")
    kinds = [c["type"] == "enum" for c in cfg["columns"]]
    if kinds != sorted(kinds, reverse=True):
        raise ValueError("enum columns must come before the numeric ones")
    return levels, n_num


def coef_names(cfg: dict) -> list:
    """Names of the p coefficients in design order, as the program's model
    names them: '<column>.<level name>' for every level but a column's
    first, then the numeric columns; 'Intercept' is kept apart."""
    out = []
    for c in cfg["columns"]:
        if c["type"] == "enum":
            out += [f"{c['name']}.{c['name']}_{i:03d}"
                    for i in range(1, int(c["levels"]))]
    return out + [c["name"] for c in cfg["columns"] if c["type"] != "enum"]


def _rounded(x, dtype):
    """x rounded to ``dtype``'s precision, kept in float32 (a convert pair
    may be dropped by the compiler as excess precision)."""
    import jax

    if dtype is None:
        return x
    assert dtype == "bfloat16"
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _design(cols, start, B, levels, n_num, mean, sd, shift):
    """(B, p + 1) float32 design of one block, intercept last. ``shift`` is
    the planted fault: the enum column of that index has its codes moved by
    one (mod its levels) before the first level is dropped."""
    import jax
    import jax.numpy as jnp

    sl = lambda c: jax.lax.dynamic_slice(c, (start,), (B,))
    parts = []
    for i, lv in enumerate(levels):
        codes = sl(cols[i]).astype(jnp.int32)
        if i == shift:
            codes = (codes + 1) % lv
        parts.append(jax.nn.one_hot(codes, lv, dtype=jnp.float32)[:, 1:])
    k = len(levels)
    for j in range(n_num):
        parts.append(((sl(cols[k + j]) - mean[j]) / sd[j])[:, None])
    parts.append(jnp.ones((B, 1), jnp.float32))
    return jnp.concatenate(parts, axis=1)


@functools.lru_cache(maxsize=8)
def _moments_fn(B: int):
    import jax
    import jax.numpy as jnp

    def moments(nums, start):
        X = jnp.stack([jax.lax.dynamic_slice(c, (start,), (B,))
                       for c in nums], axis=1)
        m = jnp.mean(X, axis=0)
        return m, jnp.sum((X - m[None, :]) ** 2, axis=0), \
            jnp.any(jnp.isnan(X))

    return jax.jit(moments)


def column_moments(nums):
    """Per numeric column mean and standard deviation (n - 1), float64."""
    n = int(nums[0].shape[0])
    B = block_rows(n)
    fn = _moments_fn(B)
    parts = [fn(tuple(nums), b * B) for b in range(n // B)]
    if any(bool(bad) for _m, _s, bad in parts):
        raise ValueError("a numeric column holds NaN: the reference does "
                         "not impute")
    means = np.stack([np.asarray(m, np.float64) for m, _s, _b in parts])
    ssq = np.stack([np.asarray(s, np.float64) for _m, s, _b in parts])
    mean = means.mean(axis=0)
    var = (ssq.sum(axis=0) + B * ((means - mean) ** 2).sum(axis=0)) / (n - 1)
    return mean, np.sqrt(var)


@functools.lru_cache(maxsize=16)
def _pass_fn(B: int, levels: tuple, n_num: int, dtype, shift, want: str):
    """One block's part of a sweep at ``beta``. ``want="step"``: (G, g,
    deviance); ``want="eta"``: (eta, deviance, max|X (beta - other)|)."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def sweep(cols, y, start, mean, sd, beta, other):
        X = _design(cols, start, B, levels, n_num, mean, sd, shift)
        yb = jax.lax.dynamic_slice(y, (start,), (B,)).astype(jnp.float32)
        Xr = _rounded(X, dtype)
        eta = jnp.dot(Xr, _rounded(beta, dtype), precision=hi)
        dev = 2.0 * jnp.sum(jnp.logaddexp(0.0, eta) - yb * eta)
        if want == "eta":
            gap = jnp.max(jnp.abs(jnp.dot(X, beta - other, precision=hi)))
            return eta, dev, gap
        mu = jax.nn.sigmoid(eta)
        w = jnp.maximum(mu * (1.0 - mu), 1e-10)
        G = jnp.dot(Xr.T, _rounded(X * w[:, None], dtype), precision=hi)
        g = jnp.dot(Xr.T, _rounded(yb - mu, dtype), precision=hi)
        return G, g, dev

    return jax.jit(sweep)


class _Problem:
    """The rows, their layout and the moments of the numeric columns."""

    def __init__(self, cols, y, cfg: dict, dtype=None, shift=None,
                 rows: int = None):
        import jax.numpy as jnp

        self.cols, self.y = tuple(cols), y
        self.levels, self.n_num = columns_of(cfg)
        self.n = int(y.shape[0]) if rows is None else int(rows)
        self.B = block_rows(self.n)
        self.dtype, self.shift = dtype, shift
        self.mean, self.sd = column_moments(self.cols[len(self.levels):]) \
            if self.n_num else (np.zeros(0), np.ones(0))
        self._m32 = jnp.asarray(self.mean, jnp.float32)
        self._s32 = jnp.asarray(self.sd, jnp.float32)
        self.p = sum(lv - 1 for lv in self.levels) + self.n_num

    def _fn(self, want):
        return _pass_fn(self.B, tuple(self.levels), self.n_num, self.dtype,
                        self.shift, want)

    def _args(self, beta, other=None):
        import jax.numpy as jnp

        b = jnp.asarray(beta, jnp.float32)
        return (self._m32, self._s32, b,
                b if other is None else jnp.asarray(other, jnp.float32))

    def step_sums(self, beta):
        """-> (G, g, deviance) at beta, float64, over the problem's rows."""
        fn, args = self._fn("step"), self._args(beta)
        parts = [fn(self.cols, self.y, b * self.B, *args)
                 for b in range(self.n // self.B)]
        G = sum(np.asarray(p_[0], np.float64) for p_ in parts)
        g = sum(np.asarray(p_[1], np.float64) for p_ in parts)
        return G, g, sum(float(p_[2]) for p_ in parts)

    def eta_sums(self, beta, other):
        """-> (eta of every row on the device, deviance, max|x (b - o)|)."""
        import jax.numpy as jnp

        fn, args = self._fn("eta"), self._args(beta, other)
        parts = [fn(self.cols, self.y, b * self.B, *args)
                 for b in range(self.n // self.B)]
        eta = jnp.concatenate([p_[0] for p_ in parts])
        return eta, sum(float(p_[1]) for p_ in parts), \
            max(float(p_[2]) for p_ in parts)

    def positives(self) -> int:
        import jax.numpy as jnp

        return int(jnp.sum(self.y[: self.n].astype(jnp.int32)))


def null_deviance(n: int, pos: int) -> float:
    ybar = pos / n
    return -2.0 * (pos * np.log(ybar) + (n - pos) * np.log1p(-ybar))


def destandardize(beta, prob: _Problem) -> np.ndarray:
    """Standardized (p coefs, intercept) -> the original scale."""
    b = np.asarray(beta, np.float64).copy()
    k = prob.p - prob.n_num
    b[-1] -= float(np.sum(b[k:prob.p] * prob.mean / prob.sd))
    b[k:prob.p] = b[k:prob.p] / prob.sd
    return b


def standardize(coef, prob: _Problem) -> np.ndarray:
    """The inverse: original scale -> the reference's standardized scale."""
    b = np.asarray(coef, np.float64).copy()
    k = prob.p - prob.n_num
    b[k:prob.p] = b[k:prob.p] * prob.sd
    b[-1] += float(np.sum(b[k:prob.p] * prob.mean / prob.sd))
    return b


def fit(cols, y, cfg: dict, precision: str = "reference",
        fault: str = None, max_iter: int = None) -> dict:
    """-> {"coef" (p + 1, original scale, intercept last), "beta" (the
    standardized scale), "iterations", "residual_deviance", "null_deviance",
    "logloss", "auc"}: what a program reports, from the reference's own
    arithmetic at ``precision`` with ``fault`` planted. ``max_iter`` caps
    the iterations of a control or a fault (the control's rounded score
    keeps its steps above ``beta_epsilon``: it would run to the
    configuration's ``max_iterations``, and is as wrong after a dozen)."""
    params = cfg["params"]
    levels, _n_num = columns_of(cfg)
    n = int(y.shape[0])
    shift = int(np.argmax(levels)) if fault == "level_shift" else None
    prob = _Problem(cols, y, cfg, PRECISIONS[precision], shift,
                    rows=n // 2 if fault == "half_batch" else None)
    pos = prob.positives()
    beta = np.zeros(prob.p + 1)
    beta[-1] = np.log(pos / (prob.n - pos))
    plain = precision == "reference" and fault is None
    eps = REF_EPS if plain else float(params.get("beta_epsilon", 1e-4))
    max_iter = 1 if fault == "one_iteration" else REF_MAX_ITER if plain \
        else int(max_iter or params.get("max_iterations", 50))
    its, last = 0, np.inf
    for its in range(1, max_iter + 1):
        G, g, _dev = prob.step_sums(beta)
        new = np.linalg.solve(G, G @ beta + g)
        delta = float(np.max(np.abs(new - beta)))
        beta = new
        # at the optimum, or as near as the float32 sums of a block let a
        # step come: steps that stopped shrinking are rounding, not descent
        if delta < eps or (plain and delta < STALL_BELOW
                           and delta > 0.5 * last):
            break
        last = delta
    eta, dev, _gap = prob.eta_sums(beta, beta)
    return {"coef": destandardize(beta, prob), "beta": beta,
            "problem": prob, "iterations": its, "residual_deviance": dev,
            "null_deviance": null_deviance(prob.n, pos),
            "logloss": dev / (2.0 * prob.n),
            "auc": auc_of(eta, prob.y[: prob.n])}


def auc_of(eta, y) -> float:
    """Area under the ROC curve of eta against the 0/1 response: the rank
    sum of the positives (Mann-Whitney) over the rows sorted by eta on the
    device, summed block by block in whole numbers on the host. Rows of
    equal eta are ranked in the order the sort leaves them (at 48M float32
    values the tied pairs of one positive and one negative are 1e-7 of all
    pairs)."""
    import jax
    import jax.numpy as jnp

    n = int(y.shape[0])
    _eta, ys = jax.lax.sort((eta, y.astype(jnp.int32)), num_keys=1)
    B = RANK_BLOCK
    pad = -n % B
    ys = jnp.pad(ys, (0, pad)).reshape(-1, B)
    local = np.asarray(jnp.sum(ys * jnp.arange(1, B + 1, dtype=jnp.int32),
                               axis=1), np.int64)
    cnt = np.asarray(jnp.sum(ys, axis=1), np.int64)
    starts = np.arange(len(cnt), dtype=np.int64) * B
    pos = int(cnt.sum())
    ranks = int((local + starts * cnt).sum())
    return (ranks - pos * (pos + 1) // 2) / (pos * (n - pos))


def coef_vector(coef, cfg: dict) -> np.ndarray:
    if isinstance(coef, dict):
        return np.array([coef[k] for k in coef_names(cfg)]
                        + [coef["Intercept"]], np.float64)
    return np.asarray(coef, np.float64)


# ---------------------------------------------------------------------------
# the interface every reference module gives the harness
# ---------------------------------------------------------------------------

def check_model(cols, y, cfg: dict, produced: dict, ref: dict = None) -> dict:
    """The numbers that decide ``correct`` for a trained model: its
    coefficients against the reference's optimum (``coef_gap`` on the
    standardized scale, a coefficient's gap over max(|b_ref|, 1);
    ``eta_gap``, the largest |x . (b - b_ref)| over the rows: what a user's
    prediction sees), and what it reported against the reference's own
    arithmetic on the model's coefficients (``deviance_gap``,
    ``logloss_gap``, ``auc_gap``) and on the response
    (``null_deviance_gap``)."""
    ref = ref or fit(cols, y, cfg)
    prob = ref["problem"]
    theirs = standardize(coef_vector(produced["coef"], cfg), prob)
    eta, dev, eta_gap = prob.eta_sums(theirs, ref["beta"])
    out = {"coef_gap": float(np.max(
               np.abs(theirs - ref["beta"])
               / np.maximum(np.abs(ref["beta"]), 1.0))),
           "eta_gap": eta_gap,
           "iterations": float(produced.get("iterations") or 0),
           "iterations_ref": float(ref["iterations"]),
           "deviance_ref": ref["residual_deviance"]}
    rel = lambda a, b: abs(float(a) - b) / abs(b)
    if produced.get("residual_deviance") is not None:
        out["deviance_gap"] = rel(produced["residual_deviance"], dev)
    if produced.get("null_deviance") is not None:
        out["null_deviance_gap"] = rel(produced["null_deviance"],
                                       ref["null_deviance"])
    reported = produced.get("reported") or {}
    if reported.get("logloss") is not None:
        out["logloss_gap"] = rel(reported["logloss"], dev / (2.0 * prob.n))
    if reported.get("auc") is not None:
        out["auc_gap"] = abs(float(reported["auc"]) - auc_of(eta, y))
    return out


def as_produced(fitted: dict) -> dict:
    """A ``fit`` in the shape the harness reads a program's model in."""
    return {"coef": fitted["coef"], "iterations": fitted["iterations"],
            "residual_deviance": fitted["residual_deviance"],
            "null_deviance": fitted["null_deviance"],
            "reported": {"logloss": fitted["logloss"],
                         "auc": fitted["auc"]}}


def controls(cols, y, cfg: dict, which=("control",) + FAULTS,
             max_iter: int = CONTROL_MAX_ITER):
    """The reference in the program's place at the lower precision, or
    broken on purpose: yields (label, numbers as the judge reads them). A
    control or fault gets ``max_iter`` iterations."""
    ref = fit(cols, y, cfg)
    for label in which:
        lower = label in PRECISIONS
        fitted = fit(cols, y, cfg,
                     precision=label if lower else "reference",
                     fault=None if lower else label, max_iter=max_iter)
        yield label, check_model(cols, y, cfg, as_produced(fitted), ref)

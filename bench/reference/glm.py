"""Plain reference for the binomial GLM a configuration states: IRLS on
standardized columns, no penalty, in float32 with every matrix product at
``HIGHEST`` and every sum over row blocks taken in float64 on the host, where
the 29 x 29 system is solved in float64. It imports nothing of the program.

    standardize: x' = (x - mean) / sd per column
    repeat: eta = X'b + b0; mu = sigmoid(eta); w = mu(1-mu);
            z = eta + (y - mu)/w;  solve (Xi' W Xi) b = Xi' W z
    until max|b - b_prev| < eps (the reference runs to a tighter eps than the
    configuration's beta_epsilon, so it stands at the optimum the program's
    iterates approach); report b on the original scale.

``fit(..., precision="control")`` is the control: the nearest precision below
the stated one — operands of every product rounded to bfloat16 (float32
accumulation), as a TPU's default matmul precision would.
"""

from __future__ import annotations

import functools

import numpy as np

from bench.reference.gbm import _block_X, _rounded, block_rows

REF_EPS = 1e-7
REF_MAX_ITER = 100
PRECISIONS = {"reference": None, "control": "bfloat16"}


@functools.lru_cache(maxsize=8)
def _moments_fn(B: int):
    import jax
    import jax.numpy as jnp

    def moments(cols, start):
        X = _block_X(cols, start, B)
        m = jnp.mean(X, axis=0)
        return m, jnp.sum((X - m[None, :]) ** 2, axis=0)

    return jax.jit(moments)


@functools.lru_cache(maxsize=8)
def _irls_pass_fn(B: int, dtype):
    """One block's part of an IRLS step on standardized columns:
    (Xi' W Xi, Xi' W z, sum of the log loss) at the given beta."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def irls_pass(cols, y, start, mean, sd, beta):
        X = (_block_X(cols, start, B) - mean[None, :]) / sd[None, :]
        Xi = jnp.concatenate([X, jnp.ones((B, 1), jnp.float32)], axis=1)
        yb = jax.lax.dynamic_slice(y, (start,), (B,)).astype(jnp.float32)
        Xr, br = _rounded(Xi, dtype), _rounded(beta, dtype)
        eta = jnp.dot(Xr, br, precision=hi)
        mu = jax.nn.sigmoid(eta)
        w = jnp.maximum(mu * (1.0 - mu), 1e-10)
        z = eta + (yb - mu) / w
        Xw = Xi * w[:, None]
        G = jnp.dot(Xr.T, _rounded(Xw, dtype), precision=hi)
        q = jnp.dot(_rounded(Xw, dtype).T, _rounded(z, dtype), precision=hi)
        ll = jnp.sum(jnp.logaddexp(0.0, eta) - yb * eta)
        return G, q, ll

    return jax.jit(irls_pass)


def column_moments(cols):
    """Per column mean and standard deviation (n - 1), in float64."""
    n = int(cols[0].shape[0])
    B = block_rows(n)
    fn = _moments_fn(B)
    parts = [fn(tuple(cols), b * B) for b in range(n // B)]
    means = np.stack([np.asarray(m, np.float64) for m, _ in parts])
    ssq = np.stack([np.asarray(s, np.float64) for _, s in parts])
    mean = means.mean(axis=0)
    var = (ssq.sum(axis=0) + B * ((means - mean) ** 2).sum(axis=0)) / (n - 1)
    return mean, np.sqrt(var)


def _sweep(cols, y, mean, sd, beta, dtype):
    import jax.numpy as jnp

    n = int(y.shape[0])
    B = block_rows(n)
    fn = _irls_pass_fn(B, dtype)
    args = (jnp.asarray(mean, jnp.float32), jnp.asarray(sd, jnp.float32),
            jnp.asarray(beta, jnp.float32))
    parts = [fn(tuple(cols), y, b * B, *args) for b in range(n // B)]
    G = sum(np.asarray(g, np.float64) for g, _q, _l in parts)
    q = sum(np.asarray(q_, np.float64) for _g, q_, _l in parts)
    ll = sum(float(l_) for _g, _q, l_ in parts) / n
    return G, q, ll


def destandardize(beta, mean, sd) -> np.ndarray:
    """Standardized (p coefs, intercept) -> the original scale."""
    b = np.asarray(beta, np.float64).copy()
    b[-1] -= float(np.sum(b[:-1] * mean / sd))
    b[:-1] = b[:-1] / sd
    return b


def fit(cols, y, params: dict, precision: str = "reference") -> dict:
    """-> {"coef": (p + 1,) on the original scale, intercept last,
    "iterations", "logloss"}."""
    import jax.numpy as jnp

    dtype = PRECISIONS[precision]
    mean, sd = column_moments(cols)
    p = len(cols)
    ybar = float(jnp.sum(y.astype(jnp.int32))) / int(y.shape[0])
    beta = np.zeros(p + 1)
    beta[-1] = np.log(ybar / (1.0 - ybar))
    eps = REF_EPS if precision == "reference" \
        else float(params.get("beta_epsilon", 1e-4))
    max_iter = REF_MAX_ITER if precision == "reference" \
        else int(params.get("max_iterations", 50))
    its = 0
    for its in range(1, max_iter + 1):
        G, q, _ll = _sweep(cols, y, mean, sd, beta, dtype)
        new = np.linalg.solve(G, q)
        delta = float(np.max(np.abs(new - beta)))
        beta = new
        if delta < eps:
            break
    _G, _q, ll = _sweep(cols, y, mean, sd, beta, dtype)
    return {"coef": destandardize(beta, mean, sd), "iterations": its,
            "logloss": ll, "mean": mean, "sd": sd}


def logloss_of(cols, y, coef) -> float:
    """Mean log loss of original-scale coefficients (intercept last) over
    every row, by the reference's own arithmetic."""
    p = len(cols)
    _G, _q, ll = _sweep(cols, y, np.zeros(p), np.ones(p), coef, None)
    return ll


def coef_vector(coef: dict, names) -> np.ndarray:
    return np.array([coef[n] for n in names] + [coef["Intercept"]],
                    np.float64)


# ---------------------------------------------------------------------------
# the interface every reference module gives the harness
# ---------------------------------------------------------------------------

def check_model(cols, y, cfg: dict, produced: dict) -> dict:
    names = [f"x{i}" for i in range(len(cols))]
    theirs = coef_vector(produced["coef"], names) \
        if isinstance(produced["coef"], dict) else np.asarray(produced["coef"])
    ref = fit(cols, y, cfg["params"])
    scale = float(np.max(np.abs(ref["coef"])))
    out = {"coef_gap": float(np.max(np.abs(theirs - ref["coef"]))) / scale,
           "iterations_ref": ref["iterations"],
           "logloss_ref": ref["logloss"]}
    reported = produced.get("reported") or {}
    if reported.get("logloss") is not None:
        ll = logloss_of(cols, y, theirs)
        out["logloss_gap"] = abs(float(reported["logloss"]) - ll) / ll
    return out


def controls(cols, y, cfg: dict, which=("control",)):
    """The reference in the program's place at the lower precision."""
    for label in which:
        fitted = fit(cols, y, cfg["params"], label)
        yield label, check_model(cols, y, cfg, {
            "coef": fitted["coef"],
            "reported": {"logloss": fitted["logloss"]}})


def predict(produced: dict, cfg: dict, *, X=None, cols=None) -> np.ndarray:
    names = [f"x{i}" for i in range(int(cfg["features"]))]
    b = coef_vector(produced["coef"], names)
    if X is None:
        X = np.stack([np.asarray(c, np.float64) for c in cols], axis=1)
    eta = np.asarray(X, np.float64) @ b[:-1] + b[-1]
    return 1.0 / (1.0 + np.exp(-eta))

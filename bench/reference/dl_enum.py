"""Plain reference for H2O Deep Learning over enum and numeric columns (the
airline deployment of szilard/benchm-ml's deep neural network section): a
dense multilayer perceptron, cross-entropy over two outputs, ADADELTA. It
imports nothing of the program and takes nothing the program made as an
input but the weights it judges; what it shares with ``glm_enum`` (the
numeric columns' moments, the block rule, the rank-sum AUC) is that
reference's own code.

A row, as the configuration's ``columns`` state them (every level kept,
H2O's ``use_all_factor_levels=true``):

    x = [onehot(enum 1), ..., onehot(enum k), (num 1 - m1) / s1, ...]

674 entries for the airline table, eight non-zero; m, s are the column's
mean and standard deviation (n - 1). The network:

    h1 = relu(x W1 + b1); h2 = relu(h1 W2 + b2); o = h2 W3 + b3
    m = o1 - o0; p1 = sigmoid(m); loss of a row = log(1 + e^m) - y m

(the softmax over two outputs and its cross-entropy, written on the margin)

built densely a block of rows at a time, straight ``jax.numpy`` in float32
with every product at ``HIGHEST``.

Training, as the configuration's ``assumed.seed_rule`` writes it down:
weights drawn UniformAdaptive (U(-l, l), l = sqrt(6 / (fan_in + fan_out)),
layer after layer from numpy ``default_rng(seed)``, biases 0); a step's rows
``jax.random.randint(kidx, (batch,), 0, rows)`` with ``key, kidx, kdrop =
jax.random.split(key, 3)`` from ``key = jax.random.PRNGKey(seed)``; the
gradient of the minibatch's mean loss; ADADELTA (Zeiler 2012, as H2O's
DeepLearningModel applies it), every parameter at every step:

    E[g2] <- rho E[g2] + (1 - rho) g^2
    dx     = sqrt(E[dx2] + eps) / sqrt(E[g2] + eps) * g
    E[dx2] <- rho E[dx2] + (1 - rho) dx^2
    w      <- w - dx

Departures from H2O, each the program's too: a minibatch of 32 rows whose
gradient is averaged (H2O: one row a step, Hogwild); the accumulators of a
first-layer weight whose input is 0 decay every step (H2O skips the zero
inputs of a sparse row).

``precision="control"`` is the control, the nearest precision below the
stated one: the operands of every product rounded to bfloat16 (float32
accumulation), as a TPU's default matmul precision would. Faults a DL job
can have: ``half_batch`` (the gradient of the first half of each minibatch),
``level_shift`` (the enum column of the most levels reads its codes off by
one), ``rho`` (0.95 in place of the configuration's rho) and
``one_step_short`` (a replay one step short).

No value is missing in the configuration's frame, and the reference does
not impute: a NaN is an error in its input.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from bench.reference.glm_enum import auc_of, block_rows, column_moments

PRECISIONS = {"reference": None, "control": "bfloat16"}
FAULTS = ("half_batch", "level_shift", "rho", "one_step_short")
FAULT_RHO = 0.95
AUC_BINS = 400              # the bins of an H2O model's reported AUC
CLEAR = 2.0 ** -19          # kink margin a short job's trajectory keeps
CLEAR_TRIES = 64


def columns_of(cfg: dict):
    """(enum levels in column order, number of numeric columns); enum
    columns come first, as the configuration lists them."""
    levels = [int(c["levels"]) for c in cfg["columns"] if c["type"] == "enum"]
    n_num = sum(1 for c in cfg["columns"] if c["type"] != "enum")
    kinds = [c["type"] == "enum" for c in cfg["columns"]]
    if kinds != sorted(kinds, reverse=True):
        raise ValueError("enum columns must come before the numeric ones")
    return levels, n_num


def dims_of(cfg: dict) -> list:
    """Widths of the layers: inputs, hidden..., 2 outputs."""
    levels, n_num = columns_of(cfg)
    return [sum(levels) + n_num] + [int(h) for h in cfg["params"]["hidden"]] \
        + [2]


def initial_weights(cfg: dict, seed: int) -> list:
    """[(W, b), ...] float32, as ``assumed.seed_rule`` draws them."""
    rng = np.random.default_rng(int(seed))
    dims = dims_of(cfg)
    out = []
    for fan_in, fan_out in zip(dims[:-1], dims[1:]):
        lim = math.sqrt(6.0 / (fan_in + fan_out))
        out.append((rng.uniform(-lim, lim, size=(fan_in, fan_out))
                    .astype(np.float32), np.zeros(fan_out, np.float32)))
    return out


def _rounded(x, dtype):
    """x rounded to ``dtype``'s precision, kept in float32."""
    import jax

    if dtype is None:
        return x
    assert dtype == "bfloat16"
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _design(cols, levels, n_num, mean, sd, shift):
    """(rows, inputs) float32 design of the rows ``cols`` hold. ``shift``
    is the planted fault: the enum column of that index has its codes moved
    by one (mod its levels)."""
    import jax
    import jax.numpy as jnp

    parts = []
    for i, lv in enumerate(levels):
        codes = cols[i].astype(jnp.int32)
        if i == shift:
            codes = (codes + 1) % lv
        parts.append(jax.nn.one_hot(codes, lv, dtype=jnp.float32))
    k = len(levels)
    for j in range(n_num):
        parts.append(((cols[k + j] - mean[j]) / sd[j])[:, None])
    return jnp.concatenate(parts, axis=1)


def _logits(weights, X, dtype):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    h = X
    for i, (W, b) in enumerate(weights):
        h = jnp.dot(_rounded(h, dtype), _rounded(W, dtype), precision=hi) + b
        if i < len(weights) - 1:
            h = jax.nn.relu(h)
    return h


def _row_loss(m, y):
    """Cross-entropy of a row over two outputs from its margin m = o1 - o0:
    -log p[y] = log(1 + e^m) - y m. Not the exponential of a log-softmax:
    on a TPU that reads p up to 3.5e-5 off a float64 forward pass where
    the sigmoid of the margin reads 9e-7 (1M rows, the initial weights)."""
    import jax.numpy as jnp

    return jnp.logaddexp(0.0, m) - y.astype(jnp.float32) * m


class _Problem:
    """The rows, their layout and the moments of the numeric columns."""

    def __init__(self, cols, y, cfg: dict, dtype=None, shift=None):
        import jax.numpy as jnp

        self.cols, self.y = tuple(cols), y
        self.levels, self.n_num = columns_of(cfg)
        self.n = int(y.shape[0])
        self.B = block_rows(self.n)
        self.dtype, self.shift = dtype, shift
        self.mean, self.sd = column_moments(self.cols[len(self.levels):]) \
            if self.n_num else (np.zeros(0), np.ones(0))
        self.m32 = jnp.asarray(self.mean, jnp.float32)
        self.s32 = jnp.asarray(self.sd, jnp.float32)


@functools.lru_cache(maxsize=16)
def _eval_fn(B: int, levels: tuple, n_num: int, dtype, shift):
    """One block: (p1, the logit difference o1 - o0, summed loss)."""
    import jax
    import jax.numpy as jnp

    def block(cols, y, start, mean, sd, weights):
        sl = lambda c: jax.lax.dynamic_slice(c, (start,), (B,))
        X = _design(tuple(sl(c) for c in cols), levels, n_num, mean, sd,
                    shift)
        o = _logits(weights, X, dtype)
        m = o[:, 1] - o[:, 0]
        return jax.nn.sigmoid(m), m, jnp.sum(_row_loss(m, sl(y)))

    return jax.jit(block)


def binned_auc(p1, y, nbins: int = AUC_BINS) -> float:
    """The area under the ROC curve as an H2O model reports it: p1 in
    ``nbins`` equal-width bins, the curve swept from the highest bin down,
    the trapezoid rule over its points. Its distance from the rank-sum AUC
    is the binning's own (1e-5 at the cell's size, up to 1e-4 at a few
    thousand rows), which would hide the arithmetic's."""
    import jax.numpy as jnp

    b = jnp.clip((p1 * nbins).astype(jnp.int32), 0, nbins - 1)
    yi = y.astype(jnp.int32)
    pos = np.asarray(jnp.zeros(nbins, jnp.int32).at[b].add(yi), np.float64)
    neg = np.asarray(jnp.zeros(nbins, jnp.int32).at[b].add(1 - yi),
                     np.float64)
    tpr = np.concatenate([[0.0], np.cumsum(pos[::-1]) / pos.sum()])
    fpr = np.concatenate([[0.0], np.cumsum(neg[::-1]) / neg.sum()])
    return float(np.sum((fpr[1:] - fpr[:-1]) * (tpr[1:] + tpr[:-1]) / 2))


def evaluate(prob: _Problem, weights) -> dict:
    """-> {"p1" (rows, on the device), "logloss", "auc" (rank sum),
    "auc_binned"} of ``weights`` over every row."""
    import jax.numpy as jnp

    fn = _eval_fn(prob.B, tuple(prob.levels), prob.n_num, prob.dtype,
                  prob.shift)
    w = [(jnp.asarray(W, jnp.float32), jnp.asarray(b, jnp.float32))
         for W, b in weights]
    parts = [fn(prob.cols, prob.y, i * prob.B, prob.m32, prob.s32, w)
             for i in range(prob.n // prob.B)]
    p1 = jnp.concatenate([q[0] for q in parts])
    margin = jnp.concatenate([q[1] for q in parts])
    loss = sum(float(q[2]) for q in parts)
    return {"p1": p1, "logloss": loss / prob.n,
            "auc": auc_of(margin, prob.y),
            "auc_binned": binned_auc(p1, prob.y)}


def _kink_margins(weights, X):
    """(hidden layers,): the smallest |z| / (sum_i |h_i W_ij| + |b_j|) of a
    layer's pre-activations over a batch; f32 rounding moves z by a small
    multiple of 6e-8 of that sum."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    h, out = X, []
    for W, b in weights[:-1]:
        z = jnp.dot(h, W, precision=hi) + b
        s = jnp.dot(jnp.abs(h), jnp.abs(W), precision=hi) + jnp.abs(b)
        out.append(jnp.min(jnp.abs(z) / jnp.maximum(s, 1e-30)))
        h = jax.nn.relu(z)
    return jnp.stack(out)


@functools.lru_cache(maxsize=16)
def _replay_fn(steps: int, batch: int, levels: tuple, n_num: int, dtype,
               shift, rho: float, eps: float, half: bool):
    """-> jitted run(...) -> (weights after ``steps`` steps, (steps,
    hidden layers) kink margins of each step's batch)."""
    import jax
    import jax.numpy as jnp

    def loss_of(weights, X, yb):
        o = _logits(weights, X, dtype)
        return jnp.mean(_row_loss(o[:, 1] - o[:, 0], yb))

    def run(cols, y, nrows, mean, sd, weights, key):
        def step(carry, _):
            weights, eg, ex, key = carry
            key, kidx, _kdrop = jax.random.split(key, 3)
            idx = jax.random.randint(kidx, (batch,), 0, nrows)
            if half:
                idx = idx[: batch // 2]
            X = _design(tuple(c[idx] for c in cols), levels, n_num, mean,
                        sd, shift)
            margins = _kink_margins(weights, X)
            g = jax.grad(loss_of)(weights, X, y[idx])
            eg = jax.tree.map(lambda e, gi: rho * e + (1 - rho) * gi * gi,
                              eg, g)
            dx = jax.tree.map(
                lambda e_x, e_g, gi: jnp.sqrt(e_x + eps)
                / jnp.sqrt(e_g + eps) * gi, ex, eg, g)
            ex = jax.tree.map(lambda e, d: rho * e + (1 - rho) * d * d,
                              ex, dx)
            weights = jax.tree.map(lambda w_, d: w_ - d, weights, dx)
            return (weights, eg, ex, key), margins

        zeros = jax.tree.map(jnp.zeros_like, weights)
        out, margins = jax.lax.scan(step, (weights, zeros, zeros, key),
                                    None, length=steps)
        return out[0], margins

    return jax.jit(run)


def replay(cols, y, cfg: dict, seed: int, steps: int,
           precision: str = "reference", fault: str = None) -> list:
    """The weights after ``steps`` minibatch steps from the seed's initial
    weights and draws, [(W, b), ...] as numpy float32."""
    return _replay(cols, y, cfg, seed, steps, precision, fault)[0]


def kink_margin(cols, y, cfg: dict, seed: int, steps: int) -> float:
    """The smallest kink margin (``_kink_margins``) over the hidden
    pre-activations of every step of the reference's replay."""
    return float(np.min(_replay(cols, y, cfg, seed, steps)[1]))


def clear_seed(cols, y, cfg: dict, seed: int, steps: int,
               tries: int = CLEAR_TRIES) -> int:
    """The first of seed, seed + 1, ... (mod 2**31 - 1, never 0) whose
    replay of ``steps`` steps keeps every hidden pre-activation further
    than ``CLEAR`` of its sum's magnitude from the Rectifier's kink; the
    clearest of ``tries`` where none does. A gate that rounding can open
    or close takes the whole minibatch row's gradient with it, and
    ADADELTA's early steps move a weight by about sqrt(eps / (1 - rho))
    whatever the gradient's size, so two sound float32 trajectories that
    meet such a gate part for good: the short job's weights can be held
    to the replay's only on a trajectory that meets none."""
    best = (-1.0, seed)
    for i in range(int(tries)):
        s = int(seed) if i == 0 else (int(seed) + i) % (2 ** 31 - 1) or 1
        m = kink_margin(cols, y, cfg, s, steps)
        if m >= CLEAR:
            return s
        best = max(best, (m, s))
    return best[1]


def _replay(cols, y, cfg: dict, seed: int, steps: int,
            precision: str = "reference", fault: str = None) -> tuple:
    """(weights as ``replay`` gives them, (steps, hidden layers) kink
    margins as numpy)."""
    import jax
    import jax.numpy as jnp

    params = cfg["params"]
    levels, n_num = columns_of(cfg)
    shift = int(np.argmax(levels)) if fault == "level_shift" else None
    prob = _Problem(cols, y, cfg, PRECISIONS[precision], shift)
    rho = FAULT_RHO if fault == "rho" else float(params["rho"])
    fn = _replay_fn(int(steps) - (fault == "one_step_short"),
                    int(params["mini_batch_size"]), tuple(levels), n_num,
                    prob.dtype, shift, rho, float(params["epsilon"]),
                    fault == "half_batch")
    w0 = [(jnp.asarray(W), jnp.asarray(b))
          for W, b in initial_weights(cfg, seed)]
    out, margins = fn(prob.cols, prob.y, prob.n, prob.m32, prob.s32, w0,
                      jax.random.PRNGKey(int(seed)))
    return ([(np.asarray(W), np.asarray(b)) for W, b in out],
            np.asarray(margins))


def weight_gap(theirs, ref, init) -> float:
    """Largest over the leaves (each layer's W and b apart) of
    |delta_theirs - delta_ref| / |delta_ref| in L2, delta = the leaf less
    its initial value: 0 for the same steps, 1 for weights that never
    moved."""
    gaps = []
    for mine, want, start in zip(theirs, ref, init):
        for t, r, z in zip(mine, want, start):
            z = np.asarray(z, np.float64)
            dt = np.asarray(t, np.float64) - z
            dr = np.asarray(r, np.float64) - z
            gaps.append(float(np.linalg.norm(dt - dr) / np.linalg.norm(dr)))
    return max(gaps)


# ---------------------------------------------------------------------------
# the interface every reference module gives the harness
# ---------------------------------------------------------------------------

def _first(cols, y, rows):
    """The first ``rows`` rows of the frame (all of them for None)."""
    if rows is None:
        return tuple(cols), y
    return tuple(c[:rows] for c in cols), y[:rows]


def check_model(cols, y, cfg: dict, produced: dict) -> dict:
    """The numbers that decide ``correct``. ``produced``: the trained
    model's ``weights`` with its ``reported`` training log loss and AUC and
    the ``p1`` a /3/Predictions of the training frame gave it; ``short``,
    a job of ``steps`` steps from ``seed`` on the frame of the first
    ``rows`` rows, and the weights it ended with.

    ``logloss_gap`` (relative) and ``auc_gap`` are the reported metrics
    against the reference's evaluation of the same weights over every row
    (the AUC as an H2O model reports it, ``binned_auc``; ``auc_rank_gap``,
    against the rank sum, is kept beside it and has no limit);
    ``prob_gap`` the largest |p1 - p1_ref| over the rows; ``weight_gap``
    the short job against the reference's replay of the same steps on the
    same rows."""
    import jax.numpy as jnp

    prob = _Problem(cols, y, cfg)
    out = {}
    ref = evaluate(prob, produced["weights"])
    reported = produced.get("reported") or {}
    if reported.get("logloss") is not None:
        out["logloss_gap"] = abs(float(reported["logloss"]) - ref["logloss"]) \
            / ref["logloss"]
    if reported.get("auc") is not None:
        out["auc_gap"] = abs(float(reported["auc"]) - ref["auc_binned"])
        out["auc_rank_gap"] = abs(float(reported["auc"]) - ref["auc"])
    if produced.get("p1") is not None:
        p1 = jnp.asarray(produced["p1"])[: prob.n]
        out["prob_gap"] = float(jnp.max(jnp.abs(p1 - ref["p1"])))
    short = produced.get("short")
    if short is not None:
        seed, steps = int(short["seed"]), int(short["steps"])
        out["weight_gap"] = weight_gap(
            short["weights"],
            replay(*_first(cols, y, short.get("rows")), cfg, seed, steps),
            initial_weights(cfg, seed))
        out["short_steps"] = float(steps)
    out["logloss_ref"] = ref["logloss"]
    return out


def as_produced(cols, y, cfg: dict, seed: int, steps: int, rows=None,
                precision: str = "reference", fault: str = None) -> dict:
    """The reference in the program's place: a replay of ``steps`` steps on
    the first ``rows`` rows at ``precision`` with ``fault`` planted, judged
    as a program's job would be (its weights are both the short job's and
    the trained model's, evaluated by the same broken arithmetic over every
    row)."""
    weights = replay(*_first(cols, y, rows), cfg, seed, steps, precision,
                     fault)
    shift = int(np.argmax(columns_of(cfg)[0])) \
        if fault == "level_shift" else None
    mine = evaluate(_Problem(cols, y, cfg, PRECISIONS[precision], shift),
                    weights)
    return {"weights": weights, "p1": mine["p1"],
            "reported": {"logloss": mine["logloss"],
                         "auc": mine["auc_binned"]},
            "short": {"seed": seed, "steps": steps, "rows": rows,
                      "weights": weights}}


def controls(cols, y, cfg: dict, seed: int, steps: int, rows=None,
             which=("control",) + FAULTS):
    """The reference in the program's place at the lower precision, or
    broken on purpose: yields (label, numbers as the judge reads them)."""
    for label in which:
        lower = label in PRECISIONS
        produced = as_produced(cols, y, cfg, seed, steps, rows,
                               precision=label if lower else "reference",
                               fault=None if lower else label)
        yield label, check_model(cols, y, cfg, produced)

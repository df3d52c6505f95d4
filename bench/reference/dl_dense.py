"""Plain reference for H2O Deep Learning on an all-numeric frame (the MNIST
deployment: H2O's documented MNIST network, 784 pixels -> 1024 -> 1024 ->
2048 -> 10, RectifierWithDropout, input dropout, L1 and max_w2): a dense
multilayer perceptron, softmax cross-entropy over the classes, ADADELTA. It
imports nothing of the program and takes nothing the program made as an
input but the weights it judges.

The design, as H2O builds it with ``ignore_const_cols`` and ``standardize``:
every column whose rows hold one value (min = max) is left out, and each
other column j is standardized by its mean m_j and standard deviation s_j
(n - 1) over the frame's rows:

    x = [(c_j - m_j) / s_j for every non-constant column j]

717 inputs for the MNIST frame. The network (H = the hidden layers):

    h_0 = x;  z_l = h_{l-1} W_l + b_l;  h_l = relu(z_l)  (l = 1 .. H)
    o = h_H W_out + b_out;  p = softmax(o);  loss of a row = -log p[y]

built densely a block of rows at a time, straight ``jax.numpy`` in float32
with every product at ``HIGHEST`` (and under
``jax.default_matmul_precision("highest")``).

Training, as the configuration's ``assumed.seed_rule`` writes it down:
weights drawn UniformAdaptive (U(-l, l), l = sqrt(6 / (fan_in + fan_out)),
layer after layer from numpy ``default_rng(seed)``, biases 0); a step's rows
``jax.random.randint(kidx, (batch,), 0, rows)`` with ``key, kidx, kdrop =
jax.random.split(key, 3)`` from ``key = jax.random.PRNGKey(seed)``; the
step's dropout masks from kdrop: ``kdrop, sub = split(kdrop)`` and the
input mask ``bernoulli(sub, 1 - input_dropout, (inputs, batch))``, then for
each hidden layer in turn ``kdrop, sub = split(kdrop)`` and its mask
``bernoulli(sub, 1 - rate, (batch, units))``; a kept value is divided by
1 - rate (inverted dropout; the input mask scales x before the product, the
bias is not scaled). The loss of the step is the batch's mean row loss plus
``l1 * sum |W|`` over every layer's weights (its subgradient sign(W),
sign(0) = 0); ADADELTA (Zeiler 2012), every parameter at every step:

    E[g2] <- rho E[g2] + (1 - rho) g^2
    dx     = sqrt(E[dx2] + eps) / sqrt(E[g2] + eps) * g
    E[dx2] <- rho E[dx2] + (1 - rho) dx^2
    w      <- w - dx

then H2O's ``max_w2`` on every layer's weights, the output layer's too: a
unit j with r2_j = sum_i W_ij^2 > max_w2 has its column scaled by
sqrt(max_w2 / r2_j); biases untouched.

Departures from H2O, each the program's too: a minibatch of 32 rows whose
gradient is averaged (H2O: one row a step, Hogwild); max_w2 on the output
layer's weights (H2O's own handling of that layer is not checked here); the
biases start at 0.

``precision="control"`` is the control, the nearest precision below the
stated one: the operands of every product rounded to bfloat16 (float32
accumulation), as a TPU's default matmul precision would. Faults a job can
have: ``no_max_w2`` (the rescale skipped), ``const_kept`` (the constant
columns kept in the design: W1 is drawn over every column), ``no_l1`` (the
L1 term dropped) and ``no_hidden_dropout`` (the hidden layers' masks
skipped).

No value is missing in the configuration's frame, and the reference does
not impute: a NaN is an error in its input.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import numpy as np

from bench.reference.dl_enum import CLEAR, CLEAR_TRIES, _rounded, weight_gap

PRECISIONS = {"reference": None, "control": "bfloat16"}
FAULTS = ("no_max_w2", "const_kept", "no_l1", "no_hidden_dropout")
MAX_BLOCK = 32_768          # rows of a block of the whole-frame evaluation


def _highest():
    import jax

    return jax.default_matmul_precision("highest")


def block_rows(n: int) -> int:
    """Largest divisor of n that is at most MAX_BLOCK (blocks tile n)."""
    for b in range(min(n, MAX_BLOCK), 0, -1):
        if n % b == 0:
            return b
    return n


class Spec(NamedTuple):
    """What the configuration (and a job's own parameters) fix of the
    network: hidden widths, classes, dropout rates, L1, max_w2, ADADELTA."""
    hidden: tuple
    classes: int
    in_drop: float
    hid_drop: tuple
    l1: float
    max_w2: float
    rho: float
    eps: float
    batch: int

    def faulty(self, fault):
        """The spec with the planted fault's parameter changed."""
        if fault == "no_max_w2":
            return self._replace(max_w2=math.inf)
        if fault == "no_l1":
            return self._replace(l1=0.0)
        if fault == "no_hidden_dropout":
            return self._replace(hid_drop=(0.0,) * len(self.hidden))
        return self


def spec_of(cfg: dict, over: dict = None) -> Spec:
    p = dict(cfg["params"], **(over or {}))
    hidden = tuple(int(h) for h in p["hidden"])
    hd = p.get("hidden_dropout_ratios")
    if hd is None:
        hd = [0.5 if "withdropout" in p["activation"].lower() else 0.0] \
            * len(hidden)
    w2 = float(p.get("max_w2", math.inf))
    return Spec(hidden, int(cfg["classes"]),
                float(p.get("input_dropout_ratio", 0.0)),
                tuple(float(r) for r in hd), float(p.get("l1", 0.0)),
                math.inf if w2 >= 3.4028235e38 else w2, float(p["rho"]),
                float(p["epsilon"]), int(p["mini_batch_size"]))


@functools.lru_cache(maxsize=8)
def _moments_fn(B: int):
    import jax
    import jax.numpy as jnp

    def moments(cols, start):
        X = jnp.stack([jax.lax.dynamic_slice(c, (start,), (B,))
                       for c in cols], axis=1)
        m = jnp.mean(X, axis=0)
        return (m, jnp.sum((X - m[None, :]) ** 2, axis=0), jnp.min(X, 0),
                jnp.max(X, 0), jnp.any(jnp.isnan(X)))

    return jax.jit(moments)


class _Problem:
    """The rows, the columns the design keeps and their moments."""

    def __init__(self, cols, y, dtype=None, keep_const=False):
        import jax.numpy as jnp

        self.cols, self.y = tuple(cols), y
        self.n = int(y.shape[0])
        self.B = block_rows(self.n)
        self.dtype = dtype
        fn = _moments_fn(self.B)
        parts = [fn(self.cols, b * self.B) for b in range(self.n // self.B)]
        if any(bool(q[4]) for q in parts):
            raise ValueError("a column holds NaN: the reference does not "
                             "impute")
        means = np.stack([np.asarray(q[0], np.float64) for q in parts])
        ssq = np.stack([np.asarray(q[1], np.float64) for q in parts])
        lo = np.min([np.asarray(q[2]) for q in parts], axis=0)
        hi = np.max([np.asarray(q[3]) for q in parts], axis=0)
        mean = means.mean(axis=0)
        var = (ssq.sum(axis=0) + self.B * ((means - mean) ** 2).sum(axis=0)) \
            / (self.n - 1)
        self.const = lo == hi
        self.live = np.arange(len(self.cols)) if keep_const \
            else np.flatnonzero(~self.const)
        sd = np.sqrt(var)
        sd[sd == 0] = 1.0                 # a kept constant column reads 0
        self.mean = jnp.asarray(mean[self.live], jnp.float32)
        self.sd = jnp.asarray(sd[self.live], jnp.float32)

    @property
    def inputs(self) -> int:
        return int(len(self.live))

    def design(self, start=0, rows=None):
        """(rows, inputs) float32 design of rows [start, start + rows)."""
        import jax
        import jax.numpy as jnp

        rows = self.n if rows is None else rows
        X = jnp.stack([jax.lax.dynamic_slice(self.cols[j], (start,), (rows,))
                       for j in self.live], axis=1)
        return (X - self.mean[None, :]) / self.sd[None, :]


def initial_weights(spec: Spec, inputs: int, seed: int) -> list:
    """[(W, b), ...] float32, as ``assumed.seed_rule`` draws them."""
    rng = np.random.default_rng(int(seed))
    dims = [inputs, *spec.hidden, spec.classes]
    out = []
    for fan_in, fan_out in zip(dims[:-1], dims[1:]):
        lim = math.sqrt(6.0 / (fan_in + fan_out))
        out.append((rng.uniform(-lim, lim, size=(fan_in, fan_out))
                    .astype(np.float32), np.zeros(fan_out, np.float32)))
    return out


def _logits(weights, X, dtype, masks=None):
    """-> (logits, [(z, magnitude) of each hidden layer]). ``masks``: None
    or (input mask (inputs, rows) or None, [hidden masks or None])."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    h, pre = X, []
    if masks is not None and masks[0] is not None:
        keep, rate = masks[0]
        h = jnp.where(keep.T, h / (1.0 - rate), 0.0)
    for i, (W, b) in enumerate(weights):
        z = jnp.dot(_rounded(h, dtype), _rounded(W, dtype), precision=hi) + b
        if i == len(weights) - 1:
            return z, pre
        pre.append((z, jnp.dot(jnp.abs(h), jnp.abs(W), precision=hi)
                    + jnp.abs(b)))
        h = jax.nn.relu(z)
        if masks is not None and masks[1][i] is not None:
            keep, rate = masks[1][i]
            h = jnp.where(keep, h / (1.0 - rate), 0.0)


def _row_loss(o, y):
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(o, axis=-1)
    return -jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None],
                                axis=-1)[:, 0]


@functools.lru_cache(maxsize=16)
def _eval_fn(B: int, live: tuple, dtype):
    """One block: (class probabilities, summed log loss, rows in error)."""
    import jax
    import jax.numpy as jnp

    def block(cols, y, start, mean, sd, weights):
        X = jnp.stack([jax.lax.dynamic_slice(cols[j], (start,), (B,))
                       for j in live], axis=1)
        X = (X - mean[None, :]) / sd[None, :]
        o, _ = _logits(weights, X, dtype)
        p = jax.nn.softmax(o, axis=-1)
        yb = jax.lax.dynamic_slice(y, (start,), (B,)).astype(jnp.int32)
        py = jnp.take_along_axis(p, yb[:, None], axis=-1)[:, 0]
        loss = -jnp.sum(jnp.log(jnp.clip(py, 1e-15, 1.0)))
        wrong = jnp.sum(jnp.argmax(p, axis=-1) != yb)
        return p, loss, wrong

    return jax.jit(block)


def evaluate(prob: _Problem, weights) -> dict:
    """-> {"probs" (rows, classes, on the device), "logloss", "err"} of
    ``weights`` over every row: the log loss of the class probabilities as
    H2O takes it (-log of p[y] clipped at 1e-15), and the share of rows
    whose most probable class is not theirs."""
    import jax.numpy as jnp

    with _highest():
        fn = _eval_fn(prob.B, tuple(int(j) for j in prob.live), prob.dtype)
        w = [(jnp.asarray(W, jnp.float32), jnp.asarray(b, jnp.float32))
             for W, b in weights]
        parts = [fn(prob.cols, prob.y, i * prob.B, prob.mean, prob.sd, w)
                 for i in range(prob.n // prob.B)]
    return {"probs": jnp.concatenate([q[0] for q in parts]),
            "logloss": sum(float(q[1]) for q in parts) / prob.n,
            "err": sum(int(q[2]) for q in parts) / prob.n}


def _masks(spec: Spec, kdrop, inputs: int):
    import jax

    k, inp = kdrop, None
    if spec.in_drop > 0:
        k, sub = jax.random.split(k)
        inp = (jax.random.bernoulli(sub, 1.0 - spec.in_drop,
                                    (inputs, spec.batch)), spec.in_drop)
    hid = []
    for units, rate in zip(spec.hidden, spec.hid_drop):
        if rate > 0:
            k, sub = jax.random.split(k)
            hid.append((jax.random.bernoulli(sub, 1.0 - rate,
                                             (spec.batch, units)), rate))
        else:
            hid.append(None)
    return inp, hid


def _max_w2(weights, max_w2):
    import jax.numpy as jnp

    out = []
    for W, b in weights:
        r2 = jnp.sum(W * W, axis=0)
        over = r2 > max_w2
        out.append((W * jnp.where(over, jnp.sqrt(max_w2 / jnp.where(
            over, r2, 1.0)), 1.0), b))
    return out


@functools.lru_cache(maxsize=16)
def _replay_fn(steps: int, spec: Spec, inputs: int, dtype):
    """-> jitted run(X, y, nrows, weights, key) -> (weights after
    ``steps`` steps, (steps, hidden layers) kink margins of each step's
    batch)."""
    import jax
    import jax.numpy as jnp

    def loss_of(weights, X, yb, masks):
        o, _ = _logits(weights, X, dtype, masks)
        pen = sum(jnp.sum(jnp.abs(W)) for W, _ in weights)
        return jnp.mean(_row_loss(o, yb)) + spec.l1 * pen

    def run(X, y, nrows, weights, key):
        def step(carry, _):
            weights, eg, ex, key = carry
            key, kidx, kdrop = jax.random.split(key, 3)
            idx = jax.random.randint(kidx, (spec.batch,), 0, nrows)
            masks = _masks(spec, kdrop, inputs)
            xb, yb = X[idx], y[idx]
            _, pre = _logits(weights, xb, None, masks)
            margins = jnp.stack([jnp.min(jnp.abs(z) / jnp.maximum(s, 1e-30))
                                 for z, s in pre])
            g = jax.grad(loss_of)(weights, xb, yb, masks)
            eg = jax.tree.map(lambda e, gi: spec.rho * e
                              + (1 - spec.rho) * gi * gi, eg, g)
            dx = jax.tree.map(
                lambda e_x, e_g, gi: jnp.sqrt(e_x + spec.eps)
                / jnp.sqrt(e_g + spec.eps) * gi, ex, eg, g)
            ex = jax.tree.map(lambda e, d: spec.rho * e
                              + (1 - spec.rho) * d * d, ex, dx)
            weights = jax.tree.map(lambda w_, d: w_ - d, weights, dx)
            if not math.isinf(spec.max_w2):
                weights = _max_w2(weights, spec.max_w2)
            return (weights, eg, ex, key), margins

        zeros = jax.tree.map(jnp.zeros_like, weights)
        out, margins = jax.lax.scan(step, (weights, zeros, zeros, key),
                                    None, length=steps)
        return out[0], margins

    return jax.jit(run)


def _replay(cols, y, cfg: dict, seed: int, steps: int,
            precision: str = "reference", fault: str = None,
            over: dict = None) -> tuple:
    """(weights after ``steps`` steps as numpy, (steps, hidden layers) kink
    margins as numpy, the problem the replay ran on)."""
    import jax
    import jax.numpy as jnp

    spec = spec_of(cfg, over).faulty(fault)
    prob = _Problem(cols, y, PRECISIONS[precision],
                    keep_const=fault == "const_kept")
    with _highest():
        fn = _replay_fn(int(steps), spec, prob.inputs, prob.dtype)
        w0 = [(jnp.asarray(W), jnp.asarray(b))
              for W, b in initial_weights(spec, prob.inputs, seed)]
        out, margins = fn(prob.design(), prob.y, prob.n, w0,
                          jax.random.PRNGKey(int(seed)))
    return ([(np.asarray(W), np.asarray(b)) for W, b in out],
            np.asarray(margins), prob)


def replay(cols, y, cfg: dict, seed: int, steps: int,
           precision: str = "reference", fault: str = None,
           over: dict = None) -> list:
    """The weights after ``steps`` minibatch steps from the seed's initial
    weights and draws, [(W, b), ...] as numpy float32; ``over`` changes
    parameters of the configuration's (a short job's own)."""
    return _replay(cols, y, cfg, seed, steps, precision, fault, over)[0]


def kink_margin(cols, y, cfg: dict, seed: int, steps: int,
                over: dict = None) -> float:
    """The smallest |z| / (sum_i |h_i W_ij| + |b_j|) over the hidden
    pre-activations of every step of the reference's replay."""
    return float(np.min(_replay(cols, y, cfg, seed, steps,
                                over=over)[1]))


def clear_seed(cols, y, cfg: dict, seed: int, steps: int,
               over: dict = None, tries: int = CLEAR_TRIES) -> int:
    """The first of seed, seed + 1, ... (mod 2**31 - 1, never 0) whose
    replay of ``steps`` steps keeps every hidden pre-activation further
    than ``CLEAR`` of its terms' magnitude from the Rectifier's kink; the
    clearest of ``tries`` where none does (``bench/reference/dl_enum.py``
    says why: a gate that rounding can open or close parts two sound
    float32 trajectories for good)."""
    best = (-1.0, seed)
    for i in range(int(tries)):
        s = int(seed) if i == 0 else (int(seed) + i) % (2 ** 31 - 1) or 1
        m = kink_margin(cols, y, cfg, s, steps, over)
        if m >= CLEAR:
            return s
        best = max(best, (m, s))
    return best[1]


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
    model's ``weights`` with its ``reported`` training log loss and
    classification error, and the (rows, classes) ``probs`` a
    /3/Predictions of the training frame gave it; ``short``, a job of
    ``steps`` steps from ``seed`` with the parameters ``over`` on the frame
    of the first ``rows`` rows, and the weights it ended with.

    ``logloss_gap`` (relative) and ``err_gap`` (absolute) are the reported
    metrics against the reference's evaluation of the same weights over
    every row; ``prob_gap`` the largest |p - p_ref| over the rows and
    classes; ``weight_gap`` the short job against the reference's replay
    of the same steps on the same rows; ``inputs`` the design's width."""
    import jax.numpy as jnp

    prob = _Problem(cols, y)
    out = {"inputs": float(prob.inputs)}
    ref = evaluate(prob, produced["weights"])
    reported = produced.get("reported") or {}
    if reported.get("logloss") is not None:
        out["logloss_gap"] = abs(float(reported["logloss"]) - ref["logloss"]) \
            / ref["logloss"]
    if reported.get("err") is not None:
        out["err_gap"] = abs(float(reported["err"]) - ref["err"])
    if produced.get("probs") is not None:
        p = jnp.asarray(produced["probs"])[: prob.n]
        out["prob_gap"] = float(jnp.max(jnp.abs(p - ref["probs"])))
    short = produced.get("short")
    if short is not None:
        seed, steps = int(short["seed"]), int(short["steps"])
        over = short.get("over")
        sub = _first(cols, y, short.get("rows"))
        out["weight_gap"] = weight_gap(
            short["weights"], replay(*sub, cfg, seed, steps, over=over),
            initial_weights(spec_of(cfg, over), _Problem(*sub).inputs, seed))
        out["short_steps"] = float(steps)
    out["logloss_ref"] = ref["logloss"]
    out["err_ref"] = ref["err"]
    return out


def as_produced(cols, y, cfg: dict, seed: int, steps: int, rows=None,
                precision: str = "reference", fault: str = None,
                over: dict = None) -> dict:
    """The reference in the program's place: a replay of ``steps`` steps on
    the first ``rows`` rows at ``precision`` with ``fault`` planted, judged
    as a program's job would be (its weights are both the short job's and
    the trained model's, evaluated by the same arithmetic over every row).
    Where the fault keeps the constant columns, the judge is handed W1's
    rows of the columns it keeps."""
    weights, _m, prob = _replay(*_first(cols, y, rows), cfg, seed, steps,
                                precision, fault, over)
    if fault == "const_kept":
        weights = [(weights[0][0][~prob.const], weights[0][1])] + weights[1:]
    mine = evaluate(_Problem(cols, y, PRECISIONS[precision]), weights)
    return {"weights": weights, "probs": mine["probs"],
            "reported": {"logloss": mine["logloss"], "err": mine["err"]},
            "short": {"seed": seed, "steps": steps, "rows": rows,
                      "over": over, "weights": weights}}


def controls(cols, y, cfg: dict, seed: int, steps: int, rows=None,
             over: dict = None, which=("control",) + FAULTS):
    """The reference in the program's place at the lower precision, or
    broken on purpose: yields (label, numbers as the judge reads them)."""
    for label in which:
        lower = label in PRECISIONS
        produced = as_produced(cols, y, cfg, seed, steps, rows,
                               precision=label if lower else "reference",
                               fault=None if lower else label, over=over)
        yield label, check_model(cols, y, cfg, produced)

"""Model metrics: AUC, confusion matrix, logloss, regression deviances.

Reference: hex/ModelMetrics*.java, hex/AUC2.java (400-bin approximate AUC,
AUC2.java:36), hex/ConfusionMatrix.java, hex/GainsLift.java. In H2O metric
builders run inside the scoring MRTask (map accumulates, reduce merges).

TPU-native design: predictions and responses are row-sharded jax.Arrays, so
every accumulation is one jitted masked reduction — XLA inserts the psum
across shards. AUC keeps the reference's fixed-bin histogram trick (400 bins
over [0,1]) because a static-shape histogram is exactly what the TPU wants:
a segment-sum instead of a sort.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

NBINS = 400  # hex/AUC2.java:36 (MAX_AUC_BINS)


# ---------------------------------------------------------------------------
# jitted accumulation kernels (compiled once per shape)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)
def _hist_matmul(nbins: int, mesh, axis):
    """One ops.segsum.segment_sum_mxu of w·y and w·(1−y) over the bins; a
    shard_map over `axis` of `mesh` where the rows are sharded (the
    (nbins, 2) sums psum'd once), plain jit on one device. Keyed by what
    repeats within a deployment; the rows per shard key the jit."""
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.compat import shard_map
    from h2o3_tpu.obs import compiles
    from h2o3_tpu.ops.segsum import segment_sum_mxu

    def binomial_hist(y, p, w):
        import jax.numpy as jnp

        def bin_of(sl):
            return jnp.clip((sl(p) * nbins).astype(jnp.int32), 0, nbins - 1)

        def cols_of(sl):
            # zero where w == 0: a NaN y on a pad or NA row adds to no bin
            wb, yb = sl(w), sl(y)
            live = wb != 0
            return (jnp.where(live, wb * yb, 0.0).astype(jnp.float32),
                    jnp.where(live, wb * (1.0 - yb), 0.0).astype(jnp.float32))

        out = segment_sum_mxu(bin_of, cols_of, n=y.shape[0], k=2,
                              nslots=nbins, axis=axis)
        return out[:, 0], out[:, 1]

    fn = binomial_hist if mesh is None else shard_map(
        binomial_hist, mesh=mesh, in_specs=(P(axis),) * 3, out_specs=(P(), P()))
    return compiles.ledgered_jit("metrics", fn, program="binomial_hist")


def _row_mesh(x):
    """(mesh, axis) of x's row sharding where it spans several devices,
    else (None, None): read from the input, so a frame on one device and
    one over four each get their own program."""
    sh = getattr(x, "sharding", None)
    spec = getattr(sh, "spec", None)
    if not spec or spec[0] is None:
        return None, None
    axis = spec[0]
    shards = int(np.prod([sh.mesh.shape[a] for a in
                          (axis if isinstance(axis, tuple) else (axis,))]))
    if shards <= 1:
        return None, None
    return sh.mesh, axis


def _binomial_hist(y, p, w, nbins: int = NBINS):
    """Per-bin (tp-candidate, fp-candidate) f32 sums: histogram of predicted
    P(class1) split by truth, weighted. Replaces AUC2's sorted-threshold
    builder; on the MXU (_hist_matmul), not as scatter-adds, which a TPU
    serializes on the rows."""
    import jax.numpy as jnp

    if y.shape[0] == 0:
        z = jnp.zeros(nbins, jnp.float32)
        return z, z
    return _hist_matmul(nbins, *_row_mesh(y))(y, p, w)


def _jit(fn):
    import jax

    return jax.jit(fn)


@_jit
def _regression_partials(y, f, w):
    import jax.numpy as jnp

    d = y - f
    wsum = jnp.sum(w)
    se = jnp.sum(w * d * d)
    ae = jnp.sum(w * jnp.abs(d))
    ysum = jnp.sum(w * y)
    y2sum = jnp.sum(w * y * y)
    sle = jnp.sum(w * (jnp.log1p(jnp.maximum(f, 0)) - jnp.log1p(jnp.maximum(y, 0))) ** 2)
    return {"wsum": wsum, "se": se, "ae": ae, "ysum": ysum, "y2sum": y2sum, "sle": sle}


@_jit
def _binomial_partials(y, p, w):
    import jax.numpy as jnp

    eps = 1e-15
    pc = jnp.clip(p, eps, 1 - eps)
    ll = -jnp.sum(w * (y * jnp.log(pc) + (1 - y) * jnp.log1p(-pc)))
    se = jnp.sum(w * (y - p) ** 2)
    wsum = jnp.sum(w)
    return {"logloss": ll, "se": se, "wsum": wsum}


@functools.partial(__import__("jax").jit, static_argnames=("nclasses",))
def _multinomial_partials(y, probs, w, nclasses: int):
    import jax.numpy as jnp

    eps = 1e-15
    yi = y.astype(jnp.int32)
    pred = jnp.argmax(probs, axis=-1).astype(jnp.int32)
    rows = jnp.arange(y.shape[0])
    py = jnp.clip(probs[rows, yi], eps, 1.0)
    ll = -jnp.sum(w * jnp.log(py))
    # confusion matrix via flat segment-sum (no atomics — SURVEY §2.10.3)
    flat = yi * nclasses + pred
    cm = jnp.zeros(nclasses * nclasses, w.dtype).at[flat].add(w)
    se = jnp.sum(w * (1.0 - py) ** 2) + jnp.sum(
        w[:, None] * jnp.where(jnp.arange(nclasses)[None, :] == yi[:, None], 0.0, probs) ** 2)
    # top-k hit counts (hit_ratio_table, 10 like reference)
    k = min(10, nclasses)
    topk = jnp.argsort(-probs, axis=-1)[:, :k]
    hits = (topk == yi[:, None])
    hitk = jnp.cumsum(hits, axis=-1).astype(w.dtype) * w[:, None]
    return {"logloss": ll, "cm": cm.reshape(nclasses, nclasses), "se": se,
            "wsum": jnp.sum(w), "hitk": jnp.sum(hitk, axis=0)}


# ---------------------------------------------------------------------------
# metric result objects (host-side, JSON-able)
# ---------------------------------------------------------------------------

@dataclass
class ConfusionMatrix:
    """hex/ConfusionMatrix.java — rows = actual, cols = predicted."""

    table: np.ndarray
    domain: List[str]

    def errors_per_class(self) -> np.ndarray:
        tot = self.table.sum(axis=1)
        correct = np.diag(self.table)
        with np.errstate(invalid="ignore", divide="ignore"):
            return np.where(tot > 0, (tot - correct) / tot, 0.0)

    @property
    def error(self) -> float:
        tot = self.table.sum()
        return float((tot - np.diag(self.table).sum()) / tot) if tot else 0.0

    def to_dict(self):
        return {"matrix": self.table.tolist(), "domain": self.domain,
                "error": self.error}


@dataclass
class AUCData:
    """hex/AUC2.java outputs: ROC from the 400-bin histogram + threshold
    criteria (max F1 etc.)."""

    auc: float
    pr_auc: float
    gini: float
    max_f1: float
    max_f1_threshold: float
    thresholds: np.ndarray = field(repr=False)
    tps: np.ndarray = field(repr=False)
    fps: np.ndarray = field(repr=False)
    p: float = 0.0
    n: float = 0.0

    def confusion_matrix(self, threshold: Optional[float] = None,
                         domain: Optional[List[str]] = None) -> ConfusionMatrix:
        thr = self.max_f1_threshold if threshold is None else threshold
        i = int(np.searchsorted(-self.thresholds, -thr))
        i = min(i, len(self.thresholds) - 1)
        tp, fp = self.tps[i], self.fps[i]
        fn, tn = self.p - tp, self.n - fp
        return ConfusionMatrix(np.array([[tn, fp], [fn, tp]]),
                               domain or ["0", "1"])


def compute_auc(pos_hist: np.ndarray, neg_hist: np.ndarray) -> AUCData:
    """ROC sweep over descending-threshold bins (AUC2.java DEFAULT criteria)."""
    # bin i covers predictions in [i/NBINS,(i+1)/NBINS); sweep from high to low
    pos = pos_hist[::-1]
    neg = neg_hist[::-1]
    tps = np.cumsum(pos)   # predicted positive at threshold <= bin upper edge
    fps = np.cumsum(neg)
    p, n = float(tps[-1]), float(fps[-1])
    if p == 0 or n == 0:
        return AUCData(0.5, 0.0, 0.0, 0.0, 0.5,
                       np.linspace(1, 0, NBINS), tps, fps, p, n)
    tpr = tps / p
    fpr = fps / n
    auc = float(np.trapezoid(np.concatenate([[0.0], tpr]), np.concatenate([[0.0], fpr])))
    with np.errstate(invalid="ignore", divide="ignore"):
        precision = np.where(tps + fps > 0, tps / (tps + fps), 1.0)
        recall = tpr
        pr_auc = float(np.trapezoid(precision, recall))
        f1 = np.where(precision + recall > 0,
                      2 * precision * recall / (precision + recall), 0.0)
    thresholds = (np.arange(NBINS, 0, -1) - 0.5) / NBINS
    best = int(np.argmax(f1))
    return AUCData(auc=auc, pr_auc=pr_auc, gini=2 * auc - 1,
                   max_f1=float(f1[best]), max_f1_threshold=float(thresholds[best]),
                   thresholds=thresholds, tps=tps, fps=fps, p=p, n=n)


@dataclass
class ModelMetrics:
    """Base (hex/ModelMetrics.java): holds what every metric set shares."""

    mse: float = float("nan")
    rmse: float = float("nan")
    nobs: float = 0.0
    description: str = ""

    def _base_dict(self):
        return {"MSE": self.mse, "RMSE": self.rmse, "nobs": self.nobs}

    def to_dict(self):
        return self._base_dict()


@dataclass
class ModelMetricsRegression(ModelMetrics):
    mae: float = float("nan")
    rmsle: float = float("nan")
    r2: float = float("nan")
    mean_residual_deviance: float = float("nan")

    def to_dict(self):
        d = self._base_dict()
        d.update({"mae": self.mae, "rmsle": self.rmsle, "r2": self.r2,
                  "mean_residual_deviance": self.mean_residual_deviance})
        return d


@dataclass
class ModelMetricsBinomial(ModelMetrics):
    logloss: float = float("nan")
    auc: float = float("nan")
    pr_auc: float = float("nan")
    gini: float = float("nan")
    mean_per_class_error: float = float("nan")
    ks: float = float("nan")              # Kolmogorov-Smirnov (GainsLift.java)
    cm: Optional[ConfusionMatrix] = None
    auc_data: Optional[AUCData] = None
    gains_lift_table = None               # TwoDimTable

    def to_dict(self):
        d = self._base_dict()
        d.update({"logloss": self.logloss, "AUC": self.auc, "pr_auc": self.pr_auc,
                  "Gini": self.gini, "mean_per_class_error": self.mean_per_class_error,
                  "ks": self.ks,
                  "cm": self.cm.to_dict() if self.cm else None,
                  "gains_lift_table": (self.gains_lift_table.to_dict()
                                       if self.gains_lift_table else None)})
        return d


@dataclass
class ModelMetricsMultinomial(ModelMetrics):
    logloss: float = float("nan")
    mean_per_class_error: float = float("nan")
    cm: Optional[ConfusionMatrix] = None
    hit_ratios: Optional[List[float]] = None

    def to_dict(self):
        d = self._base_dict()
        d.update({"logloss": self.logloss,
                  "mean_per_class_error": self.mean_per_class_error,
                  "cm": self.cm.to_dict() if self.cm else None,
                  "hit_ratio_table": self.hit_ratios})
        return d


@dataclass
class ModelMetricsAutoEncoder(ModelMetrics):
    """Reconstruction error (hex/ModelMetricsAutoEncoder: MSE over the
    expanded input space); the shared base fields are the whole surface."""


@dataclass
class ModelMetricsClustering(ModelMetrics):
    tot_withinss: float = float("nan")
    betweenss: float = float("nan")
    totss: float = float("nan")
    within_cluster_sizes: Optional[List[float]] = None

    def to_dict(self):
        d = self._base_dict()
        d.update({"tot_withinss": self.tot_withinss, "betweenss": self.betweenss,
                  "totss": self.totss})
        return d


def gains_lift(pos_hist: np.ndarray, neg_hist: np.ndarray, groups: int = 16):
    """Gains/lift table from the score histograms (hex/GainsLift.java:
    quantile groups over descending predicted probability; per-group and
    cumulative response rate / lift / capture / gain, plus the KS statistic).
    Built from the same NBINS histograms the AUC uses — one device pass
    serves both. Returns (TwoDimTable, ks)."""
    from h2o3_tpu.utils.twodim import TwoDimTable

    pos = np.asarray(pos_hist, np.float64)[::-1]      # descending p
    tot = pos + np.asarray(neg_hist, np.float64)[::-1]
    W = tot.sum()
    P = pos.sum()
    t = TwoDimTable("Gains/Lift Table",
                    ["group", "cumulative_data_fraction",
                     "lower_threshold", "response_rate", "lift",
                     "cumulative_response_rate", "cumulative_lift",
                     "capture_rate", "cumulative_capture_rate", "gain",
                     "cumulative_gain", "kolmogorov_smirnov"],
                    ["int"] + ["double"] * 11)
    if W <= 0 or P <= 0 or P >= W:
        return t, float("nan")
    rate = P / W
    nb = len(tot)
    cw = np.cumsum(tot)
    cp = np.cumsum(pos)
    ks_all = np.max(np.abs(cp / P - (cw - cp) / (W - P)))
    prev_w = prev_p = 0.0
    for g in range(1, groups + 1):
        target = W * g / groups
        i = int(np.searchsorted(cw, target - 1e-9))
        i = min(i, nb - 1)
        cum_w, cum_p = float(cw[i]), float(cp[i])
        if cum_w <= prev_w:
            continue
        gw, gp = cum_w - prev_w, cum_p - prev_p
        resp = gp / gw
        cum_resp = cum_p / cum_w
        ks = abs(cum_p / P - (cum_w - cum_p) / (W - P))
        t.add_row(g, cum_w / W, 1.0 - (i + 1) / nb, resp, resp / rate,
                  cum_resp, cum_resp / rate, gp / P, cum_p / P,
                  100 * (resp / rate - 1), 100 * (cum_resp / rate - 1), ks)
        prev_w, prev_p = cum_w, cum_p
    return t, float(ks_all)


# ---------------------------------------------------------------------------
# builders (called from Model.score / ModelBuilder scoring)
# ---------------------------------------------------------------------------

def _count_psum(y, values: int) -> None:
    """`shards` and `psum_bytes` on the span open at the call (a job's
    `metrics`, a flush's): the f32 values a shard hands the all-reduces XLA
    puts behind this pass's sums, from static shapes; 0 where `y` lies on
    one device. Host arithmetic, no device op."""
    from h2o3_tpu.obs import tracing

    shards = len(y.sharding.device_set) if hasattr(y, "sharding") else 1
    tracing.set_attrs(shards=shards)
    tracing.add_attrs(psum_bytes=4 * values if shards > 1 else 0)


def make_regression_metrics(y, f, w, distribution=None) -> ModelMetricsRegression:
    """y/f/w: row-sharded device arrays (pad rows carry w=0)."""
    import jax.numpy as jnp

    parts = {k: float(v) for k, v in _regression_partials(y, f, w).items()}
    _count_psum(y, len(parts))
    wsum = parts["wsum"]
    if wsum == 0:
        return ModelMetricsRegression()
    mse = parts["se"] / wsum
    ymean = parts["ysum"] / wsum
    ss_tot = parts["y2sum"] / wsum - ymean * ymean
    dev = mse
    if distribution is not None and distribution.name != "gaussian":
        dsum = float(jnp.sum(distribution.deviance(w, y, distribution.link(jnp.maximum(f, 1e-10))
                                                   if distribution.name in ("poisson", "gamma", "tweedie") else f)))
        dev = dsum / wsum
    return ModelMetricsRegression(
        mse=mse, rmse=float(np.sqrt(mse)), nobs=wsum,
        mae=parts["ae"] / wsum,
        rmsle=float(np.sqrt(parts["sle"] / wsum)),
        r2=1.0 - mse / ss_tot if ss_tot > 0 else float("nan"),
        mean_residual_deviance=dev)


def make_binomial_metrics(y, p, w, domain: Optional[List[str]] = None) -> ModelMetricsBinomial:
    """y in {0,1}, p = P(class 1); all row-sharded device arrays."""
    parts = {k: float(v) for k, v in _binomial_partials(y, p, w).items()}
    pos, neg = _binomial_hist(y, p, w)
    _count_psum(y, len(parts) + 2 * NBINS)
    auc = compute_auc(np.asarray(pos), np.asarray(neg))
    wsum = parts["wsum"]
    if wsum == 0:
        return ModelMetricsBinomial()
    cm = auc.confusion_matrix(domain=domain)
    mpce = float(np.mean(cm.errors_per_class()))
    mse = parts["se"] / wsum
    gl, ks = gains_lift(np.asarray(pos), np.asarray(neg))
    mm = ModelMetricsBinomial(
        mse=mse, rmse=float(np.sqrt(mse)), nobs=wsum,
        logloss=parts["logloss"] / wsum, auc=auc.auc, pr_auc=auc.pr_auc,
        gini=auc.gini, mean_per_class_error=mpce, ks=ks, cm=cm, auc_data=auc)
    mm.gains_lift_table = gl
    return mm


def make_multinomial_metrics(y, probs, w, domain: List[str]) -> ModelMetricsMultinomial:
    k = len(domain)
    parts = _multinomial_partials(y, probs, w, k)
    _count_psum(y, sum(int(v.size) for v in parts.values()))
    wsum = float(parts["wsum"])
    if wsum == 0:
        return ModelMetricsMultinomial()
    cm = ConfusionMatrix(np.asarray(parts["cm"]), list(domain))
    mse = float(parts["se"]) / wsum
    return ModelMetricsMultinomial(
        mse=mse, rmse=float(np.sqrt(mse)), nobs=wsum,
        logloss=float(parts["logloss"]) / wsum,
        mean_per_class_error=float(np.mean(cm.errors_per_class())),
        cm=cm, hit_ratios=[float(h) / wsum for h in np.asarray(parts["hitk"])])

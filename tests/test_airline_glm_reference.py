"""The airline on-time GLM (bench/configs/airline_glm_binomial.json: 6 enum +
2 numeric columns, 668 one-hot coefficients, binomial IRLS, no penalty)
against the plain reference (bench/reference/glm_enum.py) on the CPU mesh,
under the configuration's own limits; the lower-precision control and the
planted faults, which must each fail a named limit; blocked against whole
IRLS; scoring from codes against the expanded form; the spans and counters
the job brought. Counts and correctness only, never a time.

102,400 rows, not the 20,000 of the other reference tests: at 20,000 the
rarest of 300 airports have no delayed flight, the design is separated and
IRLS has no optimum (the configuration's ``assumed.dry_run_rows``)."""

import json
import os

import numpy as np
import pytest

from bench.harness import data_airline as recipe
from bench.harness import forest_enum, glm_enum as glm_reader
from bench.reference import glm as glm_ref
from bench.reference import glm_enum
from h2o3_tpu.core.frame import Column, Frame
from h2o3_tpu.models import glm as glm_mod
from h2o3_tpu.models.glm import GLM
from h2o3_tpu.obs import metrics, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 102_400
SEEDS = (3_200_000_101, 3_200_000_102, 3_200_000_103)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "bench", "configs",
                           "airline_glm_binomial.json")) as f:
        return json.load(f)


class _Sys:
    """What forest_enum and glm_enum ask of bench.harness.system.System."""

    def __init__(self, cl):
        import h2o3_tpu

        self.h2o, self.cluster = h2o3_tpu, cl

    def _check_rows(self, n):
        assert self.cluster.pad_rows(n) == n

    def model(self, model_id):
        from h2o3_tpu.core.dkv import DKV

        return DKV.get(model_id)


def _params(cfg, **over):
    p = dict(cfg["params"], **over)
    p["lambda_"] = p.pop("lambda")
    return p


def _fit(cl, cfg, seed, key, **over):
    """The recipe's frame of ``seed`` and the configuration's GLM on it."""
    from h2o3_tpu.core.dkv import DKV

    out = recipe.device_columns(seed, ROWS, sharding=cl.row_sharding())
    forest_enum.install_training_frame(
        _Sys(cl), key, recipe.frame_columns(), out[:-1], out[-1],
        recipe.RESPONSE_NAME, recipe.RESPONSE_DOMAIN)
    model = GLM(response_column=recipe.RESPONSE_NAME,
                **_params(cfg, **over)).train(training_frame=DKV.get(key))
    return out[:-1], out[-1], model


def _produced(cl, model):
    produced = glm_reader.read_glm(_Sys(cl), str(model.key))
    tm = model._output.training_metrics
    produced["reported"] = {"logloss": tm.logloss, "auc": tm.auc}
    return produced


def _over(numbers, cfg):
    return {k: (numbers[k], lim) for k, lim in cfg["limits"].items()
            if not numbers[k] <= lim}


@pytest.mark.parametrize("seed", SEEDS)
def test_program_against_the_reference_under_the_cells_limits(cl, cfg, seed):
    from h2o3_tpu.core.dkv import DKV

    key = f"airline_glm_{seed}.hex"
    cols, y, model = _fit(cl, cfg, seed, key)
    try:
        numbers = glm_enum.check_model(cols, y, cfg, _produced(cl, model))
        assert set(cfg["limits"]) <= set(numbers)
        assert not _over(numbers, cfg), numbers
        assert 3 <= model.iterations <= 12   # converged, not at max_iterations
        assert len(model.coef()) == 669
    finally:
        DKV.remove(key)
        model.delete()


MUST_FAIL = {"control": {"coef_gap"},
             "one_iteration": {"coef_gap", "eta_gap"},
             "half_batch": {"deviance_gap", "null_deviance_gap"},
             "level_shift": {"coef_gap", "logloss_gap", "auc_gap"}}


def test_control_and_planted_faults_fail_a_limit(cl, cfg):
    """The reference in the program's place, at the next lower precision
    and broken three ways, each under the cell's limits (one reference fit
    judges all four; a control or fault gets 8 iterations)."""
    out = recipe.device_columns(SEEDS[0], ROWS, sharding=cl.row_sharding())
    seen = dict(glm_enum.controls(out[:-1], out[-1], cfg, max_iter=8))
    assert set(seen) == set(MUST_FAIL)
    for label, must_fail in MUST_FAIL.items():
        assert must_fail <= set(_over(seen[label], cfg)), (label,
                                                           seen[label])


def test_one_block_against_many_blocks(cl, cfg, monkeypatch):
    """irls_block_rows is the one place the block size comes from. On a
    well-conditioned frame (4,000 rows, 5 + 3 levels, one numeric column)
    64-row blocks, the last of a shard ragged (it starts early and gives the
    rows it shares no weight), against the whole shard in one block:
    coefficients equal to 1e-6. On the airline frame 4,096-row blocks (a
    shard of 12,800 rows is three and a ragged fourth): equal to 5e-5,
    since a level of three delayed flights carries the sums' last bits into
    its coefficient a few times over."""
    from h2o3_tpu.core.dkv import DKV

    train, _test, _m = _scoring_frames()
    small = dict(response_column="y", family="binomial", lambda_=0.0)
    key = "airline_glm_blocks.hex"
    whole_s = GLM(**small).train(training_frame=train)
    _cols, _y, whole = _fit(cl, cfg, SEEDS[0], key)
    assert glm_mod.irls_block_rows(ROWS // cl.row_shards, 696) \
        == ROWS // cl.row_shards
    monkeypatch.setattr(glm_mod, "irls_block_rows", lambda n, lanes: 64)
    blocked_s = GLM(**small).train(training_frame=train)
    monkeypatch.setattr(glm_mod, "irls_block_rows", lambda n, lanes: 4096)
    blocked = GLM(response_column=recipe.RESPONSE_NAME,
                  **_params(cfg)).train(training_frame=DKV.get(key))
    try:
        for a, b, tol in ((whole_s, blocked_s, 1e-6), (whole, blocked, 5e-5)):
            assert a.iterations == b.iterations
            gap = np.max(np.abs(np.asarray(a.beta) - np.asarray(b.beta)))
            assert gap < tol, gap
            assert abs(a.residual_deviance - b.residual_deviance) \
                <= 1e-6 * a.residual_deviance
    finally:
        DKV.remove(key)
        for m in (whole_s, blocked_s, whole, blocked):
            m.delete()


def test_block_rule_at_the_cells_shapes():
    assert glm_mod.irls_block_rows(48_000_000, 696) == 65_536
    assert glm_mod.irls_block_rows(32_000_000, 30) == 131_072
    assert glm_mod.irls_block_rows(100, 696) == 100


def _scoring_frames():
    """A training frame and a test frame whose enum column holds a missing
    value and a level the model never saw, and whose numeric column a NaN;
    the test frame interns its levels in another order."""
    rng = np.random.default_rng(32)
    n = 4000
    g = rng.integers(0, 5, n)
    h = rng.integers(0, 3, n)
    x = rng.standard_normal(n)
    eff = np.array([0.0, 0.8, -0.5, 0.3, -1.0])
    p = 1 / (1 + np.exp(-(eff[g] + 0.7 * x - 0.4 * (h == 2))))
    train = Frame()
    train.add("g", Column.from_numpy(np.array(list("abcde"))[g],
                                     ctype="enum"))
    train.add("h", Column.from_numpy(np.array(["u", "v", "w"])[h],
                                     ctype="enum"))
    train.add("x", Column.from_numpy(x))
    train.add("y", Column.from_numpy(np.where(rng.random(n) < p, "Y", "N"),
                                     ctype="enum"))
    m = 512
    gt = np.array(list("edcbaz"), object)[rng.integers(0, 6, m)]
    gt[::17] = None
    xt = rng.standard_normal(m)
    xt[::13] = np.nan
    test = Frame()
    test.add("g", Column.from_numpy(gt, ctype="enum"))
    test.add("h", Column.from_numpy(
        np.array(["w", "u", "v"])[rng.integers(0, 3, m)], ctype="enum"))
    test.add("x", Column.from_numpy(xt))
    return train, test, m


def test_scoring_from_codes_equals_the_expanded_form(cl):
    """_glm_predict reads eta from codes and coefficients; the expanded
    design times beta is what it replaced. Same adapt_test: level
    remapping, mode and mean imputation."""
    train, test, m = _scoring_frames()
    model = GLM(response_column="y", family="binomial",
                lambda_=0.0).train(training_frame=train)
    try:
        adapted = model.adapt_test(test)
        arrays = tuple(c.data for c in model.dinfo.cols(adapted))
        X = np.asarray(model.dinfo.expand(*arrays), np.float64)[:m]
        b = np.asarray(model.beta, np.float64)
        want = 1 / (1 + np.exp(-(X @ b[:-1] + b[-1])))
        got = np.asarray(model._predict_raw(adapted)["probs"])[:m, 1]
        assert np.max(np.abs(got - want)) < 2e-6
        # the rows with a missing or unseen level scored as the mode's
        pred = model.predict(test)
        assert pred.nrows == m
    finally:
        model.delete()


def test_higgs_shape_against_its_reference(cl):
    """28 numeric columns, no enum: the dense form of the same program,
    against bench/reference/glm.py under higgs_glm_binomial's limit."""
    from bench.harness import data as higgs

    with open(os.path.join(ROOT, "bench", "configs",
                           "higgs_glm_binomial.json")) as f:
        hcfg = json.load(f)
    n = 20_032
    out = higgs.device_columns(3_200_000_201, n, sharding=cl.row_sharding())
    fr = Frame()
    for name, c in zip(higgs.FEATURE_NAMES, out[:-1]):
        fr.add(name, Column.from_device(c, "real", n))
    fr.add("y", Column.from_device(out[-1].astype(np.int8), "enum", n,
                                   domain=list(higgs.RESPONSE_DOMAIN)))
    p = dict(hcfg["params"])
    p["lambda_"] = p.pop("lambda")
    model = GLM(response_column="y", **p).train(training_frame=fr)
    try:
        assert glm_mod.gram_form(model.dinfo.layout()) == "dense"
        numbers = glm_ref.check_model(
            out[:-1], out[-1], hcfg,
            {"coef": {k: float(v) for k, v in model.coef().items()},
             "reported": {"logloss": model._output.training_metrics.logloss}})
        assert numbers["coef_gap"] <= hcfg["limits"]["coef_gap"], numbers
        assert numbers["logloss_gap"] < 1e-5, numbers
    finally:
        model.delete()


def test_rows_whose_mu_rounds_to_one_weigh_nothing(cl):
    """eta beyond 17 gives an f32 mu of exactly 1 and a variance of exactly
    0; such a row has no weight in IRLS (the fault that kept the HIGGS GLM
    out of the benchmark: one row in 10^8 weighed 10^10 rows' worth, so the
    error came and went with the seed and grew with the rows). Against
    Newton's method in float64 on the host."""
    rng = np.random.default_rng(7)
    n = 20_032
    X = rng.standard_normal((n, 3)) * np.array([9.0, 1.0, 1.0])
    c = np.array([1.0, -0.7, 0.4])
    eta = X @ c + 0.3
    y = (rng.random(n) < 1 / (1 + np.exp(-eta))).astype(int)
    assert np.sum(np.float32(1) / (np.float32(1) + np.exp(
        -eta.astype(np.float32))) == 1.0) > 100
    fr = Frame()
    for j in range(3):
        fr.add(f"x{j}", Column.from_numpy(X[:, j]))
    fr.add("y", Column.from_numpy(np.array(["N", "Y"])[y], ctype="enum"))
    model = GLM(response_column="y", family="binomial", lambda_=0.0,
                standardize=False).train(training_frame=fr)
    try:
        Xi = np.concatenate([X.astype(np.float32).astype(np.float64),
                             np.ones((n, 1))], axis=1)
        b = np.zeros(4)
        for _ in range(30):
            mu = 1 / (1 + np.exp(-(Xi @ b)))
            b = b + np.linalg.solve((Xi * (mu * (1 - mu))[:, None]).T @ Xi,
                                    Xi.T @ (y - mu))
        got = np.asarray(model.beta, np.float64)
        assert np.max(np.abs(got - b)) < 1e-4 * np.max(np.abs(b)), (got, b)
    finally:
        model.delete()


def _counter(name):
    m = metrics.REGISTRY.get(name)
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in m.snapshot()["samples"]}


def test_spans_and_counters_change_no_program_and_no_result(cl):
    """The trace-tree rule on a GLM job: `design`, `irls` and `metrics` end
    where the host already blocks, so a fit under an active trace compiles
    and dispatches what a plain one does and gives the same bits; the
    counters count on the host."""
    train, _test, _m = _scoring_frames()

    def fit():
        before = _counter("h2o3_backend_compiles_total")
        model = GLM(response_column="y", family="binomial",
                    lambda_=0.0).train(training_frame=train)
        after = _counter("h2o3_backend_compiles_total")
        return model, sum(after.values()) - sum(before.values())

    warm, _ = fit()
    its0 = _counter("h2o3_glm_iterations_total")
    passes0 = _counter("h2o3_glm_gram_passes_total")
    plain, plain_compiles = fit()
    with tracing.root_span("ingress", path="/3/ModelBuilders/glm") as root:
        traced, traced_compiles = fit()
    try:
        assert traced_compiles == plain_compiles
        assert np.array_equal(np.asarray(plain.beta), np.asarray(traced.beta))
        assert plain.residual_deviance == traced.residual_deviance
        spans = {s["name"]: s for s in tracing.get_trace(
            root.span["trace_id"], include_remote=False)}
        assert {"design", "irls", "metrics"} <= set(spans)
        assert spans["irls"]["attrs"] == {
            "p": 8, "gram_form": "onehot3", "row_blocks": 1,
            "iterations": traced.iterations}
        assert spans["design"]["end_ms"] <= spans["irls"]["start_ms"] \
            <= spans["irls"]["end_ms"] <= spans["metrics"]["start_ms"]
        its = sum(_counter("h2o3_glm_iterations_total").values()) \
            - sum(its0.values())
        assert its == plain.iterations + traced.iterations
        passes = _counter("h2o3_glm_gram_passes_total")
        key = (("form", "onehot3"),)
        assert passes[key] - passes0.get(key, 0.0) == its
    finally:
        for m in (warm, plain, traced):
            m.delete()


def test_mode_is_kept_on_the_column(cl):
    """DataInfo reads a categorical column's mode off the column, where it
    is computed once (by compare-and-sum, no scatter over the rows)."""
    rng = np.random.default_rng(3)
    vals = np.array(["a", "b", "c", "d"], object)[
        rng.choice(4, 5000, p=[0.1, 0.2, 0.6, 0.1])]
    vals[::7] = None
    col = Column.from_numpy(vals, ctype="enum")
    assert col._mode is None
    assert col.mode == 2 and col._mode == 2
    fr = Frame()
    fr.add("g", col)
    fr.add("y", Column.from_numpy(rng.standard_normal(5000)))
    from h2o3_tpu.models.data_info import DataInfo

    assert DataInfo(fr, response="y").cat_modes.tolist() == [2]

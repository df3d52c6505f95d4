"""H2O Deep Learning's MNIST network on an all-numeric frame
(bench/configs/mnist8m_dl_1024x1024x2048.json: 784 pixel columns, 67 of
them constant, 10 classes, RectifierWithDropout, input dropout, L1,
max_w2) against the plain reference (bench/reference/dl_dense.py) on the
CPU mesh, at 2,048 rows and hidden layers 64 / 64 / 128: forward
probabilities and log loss from the same weights; the cell's job and its
short job, with a max_w2 that binds, under the configuration's limits; the
lower-precision control and the planted faults, which must each fail a
named limit; ``ignore_const_cols``; ``max_w2`` at +inf is no change at
all; the counter of rescaled units; both parameters over REST. Counts and
correctness only, never a time.

A program over 717 stored columns takes tens of seconds to compile on the
CPU, so the cases share them: one fixture trains the cell's job and its
short job once a seed, and the other jobs run at the same rows and network
where their point allows. Only the program at max_w2 = +inf and the one
with the constant columns kept compile apart."""

import json
import math
import os

import numpy as np
import pytest

from bench.harness import data_mnist as recipe
from bench.harness import dl_dense as dl_harness
from bench.harness import dl_enum as dl_reader
from bench.reference import dl_dense
from h2o3_tpu.models import deeplearning as dl_mod
from h2o3_tpu.models.data_info import DataInfo
from h2o3_tpu.models.deeplearning import DeepLearning

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 2048
HIDDEN = [64, 64, 128]
SEEDS = (3_800_004_001, 3_800_004_002)
UNITS = sum(HIDDEN) + recipe.CLASSES


@pytest.fixture(scope="module")
def cfg():
    """The cell's configuration at the test's widths."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "mnist8m_dl_1024x1024x2048.json")) as f:
        c = json.load(f)
    c["params"] = dict(c["params"], hidden=HIDDEN)
    return c


@pytest.fixture(scope="module")
def short():
    with open(os.path.join(ROOT, "bench", "mixes",
                           "train_jobs_dl_dense.json")) as f:
        return json.load(f)["short_job"]


class _Sys:
    """What bench/harness/dl_dense and dl_enum ask of System."""

    def __init__(self, cl):
        import h2o3_tpu

        self.h2o, self.cluster = h2o3_tpu, cl

    def _check_rows(self, n):
        assert self.cluster.pad_rows(n) == n

    def model(self, model_id):
        from h2o3_tpu.core.dkv import DKV

        return DKV.get(model_id)

    def read_column(self, frame_key, column):
        from h2o3_tpu.core.dkv import DKV

        fr = DKV.get(frame_key)
        return fr.col(column).data[: fr.nrows]


def _data(cl, seed, rows=ROWS):
    out = recipe.device_columns(seed, rows, sharding=cl.row_sharding())
    return out[:-1], out[-1]


def _install(cl, key, cols, y):
    from h2o3_tpu.core.dkv import DKV

    dl_harness.install_training_frame(_Sys(cl), key, recipe.NAMES, cols, y,
                                      recipe.RESPONSE_NAME,
                                      recipe.RESPONSE_DOMAIN)
    return DKV.get(key)


def _first(cl, cols, y, n):
    import jax

    put = lambda a: jax.device_put(a[:n], cl.row_sharding())  # noqa: E731
    return tuple(put(c) for c in cols), put(y)


def _train(cfg, frame, seed, **over):
    p = dict(cfg["params"], seed=seed, **over)
    return DeepLearning(response_column=recipe.RESPONSE_NAME,
                        **p).train(training_frame=frame)


def _counter(name):
    from h2o3_tpu.obs import metrics

    m = metrics.REGISTRY.get(name)
    return sum(s["value"] for s in m.snapshot()["samples"]) if m else 0.0


@pytest.fixture(scope="module", params=SEEDS)
def cell_jobs(request, cl, cfg, short):
    """The cell's job (3 epochs on the 2,048 rows) and the mix's short job
    (input and hidden dropout, L1, a max_w2 that binds on every unit; its
    steps cross epoch ends on a frame of the first rows: three runs of the
    training program), trained once a seed for the cases that read
    them."""
    from h2o3_tpu.core.dkv import DKV

    seed = request.param
    cols, y = _data(cl, seed)
    key, skey = f"mnist_{seed}.hex", f"mnist_{seed}_short.hex"
    frame = _install(cl, key, cols, y)
    n, steps = int(short["rows"]), int(short["steps"])
    over = dict(short["params"])
    scols, sy = _first(cl, cols, y, n)
    js = dl_dense.clear_seed(scols, sy, cfg, seed % (2 ** 31 - 1), steps,
                             over)
    model = _train(cfg, frame, seed % (2 ** 31 - 1), epochs=3.0)
    job = _train(cfg, _install(cl, skey, scols, sy), js,
                 epochs=steps * cfg["params"]["mini_batch_size"] / n, **over)
    yield {"seed": seed, "cols": cols, "y": y, "frame": frame,
           "model": model, "job": job, "js": js, "steps": steps, "rows": n,
           "over": over}
    for k in (key, skey):
        DKV.remove(k)
    model.delete()
    job.delete()


def test_forward_and_logloss_from_the_same_weights(cl, cfg, cell_jobs):
    """The program's two whole-frame passes (the class probabilities and
    the summed loss) against the reference's evaluation of the same seeded
    weights over every row."""
    seed, cols, y = cell_jobs["seed"], cell_jobs["cols"], cell_jobs["y"]
    frame = cell_jobs["frame"]
    di = DataInfo(frame, response=recipe.RESPONSE_NAME,
                  use_all_factor_levels=True, ignore_const_cols=True)
    spec = dl_dense.spec_of(cfg)
    prob = dl_dense._Problem(cols, y)
    assert di.fullN == prob.inputs == 717
    weights = dl_dense.initial_weights(spec, prob.inputs, seed)
    rng = np.random.default_rng(seed)
    weights = [(W, rng.normal(0, 0.1, b.shape).astype(np.float32))
               for W, b in weights]
    net = dl_mod._Net(di.layout(), "rectifierwithdropout",
                      recipe.CLASSES, False)
    import jax.numpy as jnp

    params = [(jnp.asarray(W), jnp.asarray(b)) for W, b in weights]
    arrays = tuple(c.data for c in di.cols(frame))
    probs = dl_mod._run_pass(net, "predict", params, di.moments(),
                             arrays)[:ROWS]
    w = (jnp.arange(arrays[0].shape[0]) < ROWS).astype(jnp.float32)
    codes = jnp.maximum(frame.col(recipe.RESPONSE_NAME).data, 0)
    lw, sw = dl_mod._run_pass(net, "loss", params, di.moments(),
                              arrays + (codes.astype(jnp.int32), w))
    ref = dl_dense.evaluate(prob, weights)
    assert float(jnp.max(jnp.abs(probs - ref["probs"]))) < 1e-5
    # the pass's loss is -log_softmax, the reference's -log p clipped:
    # the same at the initial weights' moderate probabilities
    assert float(lw / sw) == pytest.approx(ref["logloss"], rel=1e-5)
    assert float(sw) == ROWS


def test_program_against_the_reference_under_the_cells_limits(cl, cfg,
                                                               cell_jobs):
    """The cell's job and its check at the test's size: the trained
    model's reported multinomial metrics and predictions against the
    reference's evaluation of its weights, and the mix's short job against
    the reference's replay."""
    from h2o3_tpu.core.dkv import DKV

    j = cell_jobs
    model, job = j["model"], j["job"]
    batch = cfg["params"]["mini_batch_size"]
    steps, n = j["steps"], j["rows"]
    try:
        assert [h["epoch"] for h in job._output.scoring_history] == \
            [min(e, steps * batch / n) for e in
             range(1, -(-steps * batch // n) + 1)]
        produced = dl_reader.read_dl(_Sys(cl), str(model.key))
        tm = model._output.training_metrics
        assert len(tm.hit_ratios) == recipe.CLASSES
        produced["reported"] = {"logloss": tm.logloss, "err": tm.cm.error}
        pred = model.predict(j["frame"])
        DKV.put("mnist_pred.hex", pred)
        produced["probs"] = dl_harness.read_probs(_Sys(cl), "mnist_pred.hex",
                                                  recipe.RESPONSE_DOMAIN)
        produced["short"] = {
            "seed": j["js"], "steps": steps, "rows": n, "over": j["over"],
            "weights": dl_reader.read_dl(_Sys(cl), str(job.key))["weights"]}
        numbers = dl_dense.check_model(j["cols"], j["y"], cfg, produced)
        assert set(cfg["limits"]) <= set(numbers)
        # a row whose two likeliest classes tie within rounding may flip its
        # argmax: at 2,048 rows that one row is 4.9e-4 of err, at the
        # cell's 2,025,000 it is 4.9e-7
        limits = dict(cfg["limits"],
                      err_gap=max(cfg["limits"]["err_gap"], 1.0 / ROWS))
        over_limit = {k: (numbers[k], lim) for k, lim in limits.items()
                      if not numbers[k] <= lim}
        assert not over_limit, numbers
        assert [W.shape for W, _ in produced["weights"]] == \
            [(717, 64), (64, 64), (64, 128), (128, 10)]
        assert numbers["err_ref"] < 0.6     # it learned: chance is 0.9
    finally:
        DKV.remove("mnist_pred.hex")


MUST_FAIL = {"control": {"logloss_gap", "weight_gap", "prob_gap"},
             "no_max_w2": {"weight_gap"},
             "const_kept": {"weight_gap"},
             "no_l1": {"weight_gap"},
             "no_hidden_dropout": {"weight_gap"}}


@pytest.fixture(scope="module")
def short_problem(cl, cfg, short):
    """The frame, the short job's rows and steps, its parameters and the
    clear seed the harness would post for it."""
    cols, y = _data(cl, SEEDS[0])
    n, steps = int(short["rows"]), int(short["steps"])
    over = dict(short["params"])
    scols, sy = _first(cl, cols, y, n)
    js = dl_dense.clear_seed(scols, sy, cfg, SEEDS[0] % (2 ** 31 - 1), steps,
                             over)
    return cols, y, js, steps, n, over


@pytest.mark.parametrize("label", sorted(MUST_FAIL))
def test_control_and_planted_faults_fail_a_limit(cfg, short_problem, label):
    """The reference in the program's place at the next lower precision
    and broken four ways, each judged under the cell's limits."""
    cols, y, js, steps, n, over = short_problem
    (_label, numbers), = dl_dense.controls(cols, y, cfg, js, steps, n, over,
                                           which=(label,))
    failed = {k for k, lim in cfg["limits"].items() if not numbers[k] <= lim}
    assert MUST_FAIL[label] <= failed, (label, numbers)


def test_ignore_const_cols_drops_the_constant_predictors(cl, cfg):
    """The 67 dead pixels, an all-NA column and a one-level enum leave the
    design; W1 has a row for each kept column and its initial scale follows
    that fan-in; the model lists the dropped names and scores a frame that
    lacks them. Off, every column stays."""
    import jax.numpy as jnp

    from h2o3_tpu.core.dkv import DKV
    from h2o3_tpu.core.frame import Column

    cols, y = _data(cl, SEEDS[0])
    key = "mnist_const.hex"
    frame = _install(cl, key, cols, y)
    frame.add("all_na", Column.from_numpy(np.full(ROWS, np.nan,
                                                  np.float32)))
    frame.add("one_level", Column.from_numpy(np.array(["a"] * ROWS),
                                             ctype="enum"))
    dead = [recipe.NAMES[j] for j in recipe.dead_pixels()]
    try:
        di = DataInfo(frame, response=recipe.RESPONSE_NAME,
                      ignore_const_cols=True)
        assert list(di.const_cols) == dead + ["all_na", "one_level"]
        assert di.fullN == 717 and not di.cat_names
        kept = DataInfo(frame, response=recipe.RESPONSE_NAME)
        assert kept.const_cols == () and kept.fullN == 786
        # one step: the weights sit within 1e-3 of their draw
        m = _train(cfg, frame, 7, epochs=32 / ROWS)
        W1 = np.asarray(m.params_tree[0][0])
        assert W1.shape == (717, 64)
        lim = lambda fan_in: math.sqrt(6.0 / (fan_in + 64))  # noqa: E731
        assert lim(784) + 2e-3 < np.abs(W1).max() < lim(717) + 2e-3
        assert m._output.const_cols_dropped == dead + ["all_na",
                                                       "one_level"]
        assert not set(dead) & set(m._output.names)
        DKV.put("mnist_const_score.hex", frame.drop(dead + ["all_na"]))
        p = m.predict(DKV.get("mnist_const_score.hex"))
        q = m.predict(frame)
        assert np.array_equal(np.asarray(p.col("3").data),
                              np.asarray(q.col("3").data))
        off = _train(cfg, frame, 7, epochs=32 / ROWS,
                     ignore_const_cols=False)
        assert np.asarray(off.params_tree[0][0]).shape == (786, 64)
        assert float(jnp.sum(off.params_tree[0][0][0] ** 2)) > 0
        m.delete()
        off.delete()
    finally:
        DKV.remove(key)
        DKV.remove("mnist_const_score.hex")


@pytest.mark.parametrize("max_w2", [math.inf, 3.4028235e38])
def test_max_w2_off_is_bit_for_bit_no_rescale(cl, cfg, max_w2):
    """At +inf (or Float.MAX_VALUE, H2O's clients' default) the training
    program is the one a build without the parameter runs: the same bits,
    and no unit counted as rescaled."""
    from h2o3_tpu.core.dkv import DKV

    cols, y = _data(cl, SEEDS[1], rows=256)
    key = f"mnist_inf_{max_w2:g}.hex"
    frame = _install(cl, key, cols, y)
    params = {k: v for k, v in cfg["params"].items() if k != "max_w2"}
    try:
        c0 = _counter("h2o3_dl_max_w2_rescaled_total")
        plain = DeepLearning(response_column=recipe.RESPONSE_NAME, seed=9,
                             **dict(params, epochs=0.5)).train(
                                 training_frame=frame)
        capped = DeepLearning(response_column=recipe.RESPONSE_NAME, seed=9,
                              **dict(params, epochs=0.5,
                                     max_w2=max_w2)).train(
                                         training_frame=frame)
        for (a, b), (c, d) in zip(plain.params_tree, capped.params_tree):
            assert np.array_equal(np.asarray(a), np.asarray(c))
            assert np.array_equal(np.asarray(b), np.asarray(d))
        assert _counter("h2o3_dl_max_w2_rescaled_total") == c0
        plain.delete()
        capped.delete()
    finally:
        DKV.remove(key)


def test_training_program_holds_the_rescale_only_when_finite(cl):
    """The compile key: a net at the default max_w2 lowers to a program of
    three outputs and no rescale; a finite one carries the count out."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.models.data_info import DesignLayout

    net = dl_mod._Net(DesignLayout((), 0, 6, True), "rectifier", 3, False)
    params = dl_mod._init_params(6, [8], 3, 1, "UniformAdaptive", 1.0)
    opt_state = dl_mod._optimizer(net).init(params)
    arrays = tuple(jnp.zeros(64, jnp.float32) for _ in range(6))
    moments = (np.zeros(0, np.int32), np.zeros(6, np.float32),
               np.zeros(6, np.float32), np.ones(6, np.float32))
    args = (params, opt_state, jax.random.PRNGKey(0), 3, 64, arrays, moments,
            jnp.zeros(64, jnp.int32), jnp.ones(64, jnp.float32))
    assert net.max_w2 == math.inf
    off = dl_mod._dl_train_steps.lower(*args, net=net)
    on = dl_mod._dl_train_steps.lower(*args, jnp.int32(0),
                                      net=net._replace(max_w2=1.0))
    assert len(off.out_info) == 3 and len(on.out_info) == 4
    assert off.as_text() != on.as_text()
    assert off.as_text() == dl_mod._dl_train_steps.lower(
        *args, None, net=net).as_text()


def test_the_counter_counts_rescaled_units(cl, cfg, short):
    """A first step rescales the units whose squared norm is over the
    short job's cap after the step's update, and the update moves a norm
    by far less than 0.02 here: so the counter's one step lies between the
    counts of initial norms over cap + 0.02 and over cap - 0.02 (at these
    widths about 1.8 / 1.0 / 0.67 / 1.9 by layer against a cap of 0.5).
    A job of the short job's 20 steps in three runs of the training
    program rescales at least those and at most 20 a unit, summed on the
    device and added once, and leaves every unit at the cap. Both jobs are
    the short job's program: its frame's rows, its cap."""
    from h2o3_tpu.core.dkv import DKV

    cap = float(short["params"]["max_w2"])
    seed = 5
    n, steps = int(short["rows"]), int(short["steps"])
    cols, y = _data(cl, SEEDS[0], rows=n)
    key = "mnist_counter.hex"
    frame = _install(cl, key, cols, y)
    try:
        r2 = np.concatenate([
            np.sum(np.asarray(W, np.float64) ** 2, axis=0)
            for W, _b in dl_mod._init_params(717, HIDDEN, recipe.CLASSES,
                                             seed, "UniformAdaptive", 1.0)])
        assert r2.size == UNITS
        c0 = _counter("h2o3_dl_max_w2_rescaled_total")
        one = _train(cfg, frame, seed, epochs=32 / n, max_w2=cap)
        c1 = _counter("h2o3_dl_max_w2_rescaled_total")
        first = c1 - c0
        assert (r2 > cap + 0.02).sum() <= first <= (r2 > cap - 0.02).sum()
        assert first > UNITS // 2
        many = _train(cfg, frame, seed, epochs=steps * 32 / n, max_w2=cap)
        assert first <= _counter("h2o3_dl_max_w2_rescaled_total") - c1 \
            <= steps * UNITS
        for W, _b in many.params_tree:
            r2 = np.sum(np.asarray(W, np.float64) ** 2, axis=0)
            assert r2.max() <= cap * (1 + 1e-5)
        for m in (one, many):
            m.delete()
    finally:
        DKV.remove(key)


def test_rest_builder_takes_max_w2_and_ignore_const_cols(cl, cfg):
    """POST /3/ModelBuilders/deeplearning with both parameters trains on
    the all-numeric frame; GET lists them with H2O's defaults; the model
    reports multinomial training metrics and scores through
    /3/Predictions; the Python estimator takes them too."""
    import h2o3_tpu
    from bench.harness.rest import Rest
    from h2o3_tpu.core.dkv import DKV
    from h2o3_tpu.estimators import H2ODeepLearningEstimator

    cols, y = _data(cl, SEEDS[1])
    key = "mnist_rest.hex"
    _install(cl, key, cols, y)
    srv = h2o3_tpu.start_server(port=0)
    try:
        rest = Rest(srv.port)
        params = {p["name"]: p for p in rest(
            "GET", "/3/ModelBuilders/deeplearning")["model_builders"]
            ["deeplearning"]["parameters"]}
        assert params["ignore_const_cols"]["default_value"] is True
        assert params["max_w2"]["default_value"] in (None, math.inf)
        body = dict(cfg["params"], training_frame=key, seed=3,
                    response_column=recipe.RESPONSE_NAME,
                    model_id="mnist_rest_model", epochs=0.25, max_w2=10.0)
        rec = rest.run_job("deeplearning", body)
        assert rec["status"] == "DONE", rec
        m = DKV.get("mnist_rest_model")
        assert np.asarray(m.params_tree[0][0]).shape == (717, 64)
        doc = rest("GET", "/3/Models/mnist_rest_model")["models"][0]
        tm = doc["output"]["training_metrics"]
        assert tm["__meta"]["schema_type"] == "ModelMetricsMultinomial"
        assert tm["logloss"] > 0 and tm["hit_ratio_table"]
        rest("POST", f"/3/Predictions/models/mnist_rest_model/frames/{key}",
             data={"predictions_frame": "mnist_rest.pred"})
        pred = DKV.get("mnist_rest.pred")
        assert pred.names == ["predict"] + list(recipe.RESPONSE_DOMAIN)
        est = H2ODeepLearningEstimator(max_w2=5.0, ignore_const_cols=False)
        assert est.params["max_w2"] == 5.0
        assert est.params["ignore_const_cols"] is False
        m.delete()
    finally:
        srv.stop()
        for k in (key, "mnist_rest.pred"):
            DKV.remove(k)

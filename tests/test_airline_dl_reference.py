"""H2O Deep Learning on the airline on-time table
(bench/configs/airline_dl_200x200.json: 6 enum + 2 numeric columns, 674
inputs -> 200 -> 200 -> 2, Rectifier, ADADELTA, minibatch 32) against the
plain reference (bench/reference/dl_enum.py) on the CPU mesh, under the
configuration's own limits; the lower-precision control and the planted
faults, which must each fail a named limit; the first layer from codes
against the expanded design; the compiled programs' memory at 1M rows; the
draws over a frame that does not tile the mesh. Counts and correctness
only, never a time."""

import json
import os

import numpy as np
import pytest

from bench.harness import data_airline as recipe
from bench.harness import dl_enum as dl_reader
from bench.harness import forest_enum
from bench.reference import dl_enum
from h2o3_tpu.core.frame import Column, Frame
from h2o3_tpu.models import deeplearning as dl_mod
from h2o3_tpu.models.data_info import DataInfo, first_layer
from h2o3_tpu.models.deeplearning import DeepLearning

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (3_800_000_101, 3_800_000_102, 3_800_000_103)


@pytest.fixture(scope="module")
def short():
    """The mix's short job: its steps and the rows of its frame."""
    with open(os.path.join(ROOT, "bench", "mixes",
                           "train_jobs_dl_enum.json")) as f:
        return json.load(f)["short_job"]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "bench", "configs",
                           "airline_dl_200x200.json")) as f:
        return json.load(f)


class _Sys:
    """What forest_enum and dl_enum ask of bench.harness.system.System."""

    def __init__(self, cl):
        import h2o3_tpu

        self.h2o, self.cluster = h2o3_tpu, cl

    def _check_rows(self, n):
        assert self.cluster.pad_rows(n) == n

    def model(self, model_id):
        from h2o3_tpu.core.dkv import DKV

        return DKV.get(model_id)


def _train(cfg, frame, seed, job_seed=None, **over):
    p = dict(cfg["params"], seed=job_seed or recipe.fold_seed(seed)[0],
             **over)
    return DeepLearning(response_column=recipe.RESPONSE_NAME,
                        **p).train(training_frame=frame)


def _install(cl, key, cols, y):
    from h2o3_tpu.core.dkv import DKV

    forest_enum.install_training_frame(
        _Sys(cl), key, recipe.frame_columns(), cols, y,
        recipe.RESPONSE_NAME, recipe.RESPONSE_DOMAIN)
    return DKV.get(key)


def _over(numbers, cfg):
    return {k: (numbers[k], lim) for k, lim in cfg["limits"].items()
            if not numbers[k] <= lim}


@pytest.mark.parametrize("seed", SEEDS)
def test_program_against_the_reference_under_the_cells_limits(cl, cfg, short,
                                                               seed):
    """The cell's job and its check at dry_run_rows: the trained model's
    reported metrics and predictions against the reference's evaluation of
    its weights, and the mix's short job (its steps cross epoch ends on a
    frame of the first rows) against the reference's replay."""
    import jax

    from h2o3_tpu.core.dkv import DKV

    key, skey = f"airline_dl_{seed}.hex", f"airline_dl_{seed}_short.hex"
    out = recipe.device_columns(seed, int(cfg["dry_run_rows"]),
                                sharding=cl.row_sharding())
    cols, y = out[:-1], out[-1]
    frame = _install(cl, key, cols, y)
    n, steps = int(short["rows"]), int(short["steps"])
    first = [jax.device_put(c[:n], cl.row_sharding()) for c in out]
    batch = cfg["params"]["mini_batch_size"]
    model = _train(cfg, frame, seed)
    js = dl_enum.clear_seed(first[:-1], first[-1], cfg,
                            recipe.fold_seed(seed)[0], steps)
    job = _train(cfg, _install(cl, skey, first[:-1], first[-1]), seed,
                 job_seed=js, epochs=steps * batch / n)
    try:
        assert model.epochs_trained == cfg["params"]["epochs"]
        # whole epochs and a partial last one: one loss pass each
        assert [h["epoch"] for h in job._output.scoring_history] == \
            [min(e, steps * batch / n) for e in
             range(1, -(-steps * batch // n) + 1)]
        tm = model._output.training_metrics
        produced = dl_reader.read_dl(_Sys(cl), str(model.key))
        produced["reported"] = {"logloss": tm.logloss, "auc": tm.auc}
        produced["p1"] = model.predict(frame).col("Y").data
        produced["short"] = {
            "seed": js, "steps": steps, "rows": n,
            "weights": dl_reader.read_dl(_Sys(cl), str(job.key))["weights"]}
        numbers = dl_enum.check_model(cols, y, cfg, produced)
        assert set(cfg["limits"]) <= set(numbers)
        assert not _over(numbers, cfg), numbers
        assert [W.shape for W, _ in produced["weights"]] == \
            [(674, 200), (200, 200), (200, 2)]
    finally:
        DKV.remove(key)
        DKV.remove(skey)
        model.delete()
        job.delete()


MUST_FAIL = {"control": {"weight_gap", "prob_gap"},
             "half_batch": {"weight_gap"},
             "level_shift": {"weight_gap", "prob_gap", "logloss_gap",
                             "auc_gap"},
             "rho": {"weight_gap"},
             "one_step_short": {"weight_gap"}}


def test_control_and_planted_faults_fail_a_limit(cl, cfg, short):
    """The reference in the program's place, at the next lower precision
    and broken four ways, each judged under the cell's limits."""
    rows = int(cfg["dry_run_rows"])
    n, steps = int(short["rows"]), int(short["steps"])
    out = recipe.device_columns(SEEDS[0], rows, sharding=cl.row_sharding())
    js = dl_enum.clear_seed([c[:n] for c in out[:-1]], out[-1][:n], cfg,
                            recipe.fold_seed(SEEDS[0])[0], steps)
    seen = dict(dl_enum.controls(out[:-1], out[-1], cfg, js, steps, n))
    assert set(seen) == set(MUST_FAIL)
    for label, must_fail in MUST_FAIL.items():
        assert must_fail <= set(_over(seen[label], cfg)), (label,
                                                           seen[label])


def test_kink_margin_is_the_smallest_relative_pre_activation():
    """The reference's kink margin of a batch against float64: the least
    |z| / (sum_i |h_i W_ij| + |b_j|) over the hidden layers' units."""
    rng = np.random.default_rng(38)
    dims = (9, 6, 5, 2)
    ws = [(rng.standard_normal((a, b)).astype(np.float32),
           rng.standard_normal(b).astype(np.float32))
          for a, b in zip(dims[:-1], dims[1:])]
    X = rng.standard_normal((7, dims[0])).astype(np.float32)
    h, want = X.astype(np.float64), []
    for W, b in ws[:-1]:
        z = h @ W + b
        want.append(np.min(np.abs(z) / (np.abs(h) @ np.abs(W) + np.abs(b))))
        h = np.maximum(z, 0.0)
    got = np.asarray(dl_enum._kink_margins(ws, X))
    assert got.shape == (2,)
    np.testing.assert_allclose(got, want, rtol=1e-4)


@pytest.mark.parametrize("seed", (18, 26))
def test_the_short_job_runs_on_a_seed_clear_of_the_kink(cl, cfg, short, seed):
    """Frames of the mix's short-job rows whose job seed meets a Rectifier
    gate within float32 rounding of its kink (a margin near 2.5e-8): the
    check moves the short job to the first later seed whose replay keeps
    CLEAR, and there the program's weights are the replay's."""
    import jax

    from h2o3_tpu.core.dkv import DKV

    n, steps = int(short["rows"]), int(short["steps"])
    out = [jax.device_put(c, cl.row_sharding())
           for c in recipe.device_columns(seed, n)]
    js = recipe.fold_seed(seed)[0]
    assert dl_enum.kink_margin(out[:-1], out[-1], cfg, js, steps) \
        < dl_enum.CLEAR / 10
    clear = dl_enum.clear_seed(out[:-1], out[-1], cfg, js, steps)
    assert clear > js
    assert dl_enum.kink_margin(out[:-1], out[-1], cfg, clear, steps) \
        >= dl_enum.CLEAR
    assert all(dl_enum.kink_margin(out[:-1], out[-1], cfg, s, steps)
               < dl_enum.CLEAR for s in range(js, clear))
    key = f"airline_dl_clear_{seed}.hex"
    job = _train(cfg, _install(cl, key, out[:-1], out[-1]), seed,
                 job_seed=clear,
                 epochs=steps * cfg["params"]["mini_batch_size"] / n)
    try:
        gap = dl_enum.weight_gap(
            dl_reader.read_dl(_Sys(cl), str(job.key))["weights"],
            dl_enum.replay(out[:-1], out[-1], cfg, clear, steps),
            dl_enum.initial_weights(cfg, clear))
        assert gap < cfg["limits"]["weight_gap"] / 1000, gap
    finally:
        DKV.remove(key)
        job.delete()


def _scoring_frames():
    """A training frame and a test frame whose enum column holds a missing
    value and a level the model never saw, and whose numeric column a NaN;
    the test frame interns its levels in another order."""
    rng = np.random.default_rng(38)
    n = 1000
    train = Frame()
    train.add("g", Column.from_numpy(
        np.array(list("abcde"))[rng.integers(0, 5, n)], ctype="enum"))
    train.add("h", Column.from_numpy(
        np.array(["u", "v", "w"])[rng.integers(0, 3, n)], ctype="enum"))
    train.add("x", Column.from_numpy(rng.standard_normal(n)))
    train.add("y", Column.from_numpy(
        np.array(["N", "Y"])[rng.integers(0, 2, n)], ctype="enum"))
    m = 300
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


@pytest.mark.parametrize("all_levels", [True, False])
def test_first_layer_equals_the_expanded_form(cl, all_levels):
    """first_layer reads x @ W1 + b1 from codes; the expanded design times
    W1 is what it replaced. A test frame adapted by a trained model to its
    domains: an unseen level and a missing code read the mode, a NaN the
    mean; with the first level dropped its code reads no row."""
    train, test, m = _scoring_frames()
    model = DeepLearning(hidden=[4], epochs=1, seed=1,
                         use_all_factor_levels=all_levels).train(
        y="y", training_frame=train)
    try:
        di = model.data_info
        assert di.use_all_factor_levels == all_levels
        adapted = model.adapt_test(test)
        arrays = tuple(c.data for c in di.cols(adapted))
        assert int(np.sum(np.asarray(arrays[0])[:m] < 0)) > 20
        rng = np.random.default_rng(5)
        W = rng.standard_normal((di.fullN, 7)).astype(np.float32)
        b = rng.standard_normal(7).astype(np.float32)
        X = np.asarray(di.expand(*arrays), np.float64)[:m]
        want = X @ W.astype(np.float64) + b
        got = np.asarray(first_layer(di.layout(), di.moments(), arrays, W,
                                     b))[:m]
        assert got.shape == (m, 7)
        assert np.max(np.abs(got - want)) < 2e-6 * np.abs(want).max()
        # and the model's own scoring, which reads it, against the dense
        # forward pass of the same weights
        (W1, b1), (W2, b2) = [(np.asarray(a, np.float64),
                               np.asarray(c, np.float64))
                              for a, c in model.params_tree]
        o = np.maximum(X @ W1 + b1, 0.0) @ W2 + b2
        p1 = 1.0 / (1.0 + np.exp(o[:, 0] - o[:, 1]))
        got_p1 = np.asarray(model.predict(test).col("Y").data)[:m]
        assert np.max(np.abs(got_p1 - p1)) < 2e-6
    finally:
        model.delete()


def test_ignore_const_cols_keeps_the_airline_design(cl, cfg):
    """H2O's default ``ignore_const_cols`` on the cell's frame at
    dry_run_rows: no predictor of the airline recipe is constant, so the
    design keeps its 674 inputs and the layout the training program is
    compiled for is the one without the parameter."""
    from h2o3_tpu.core.dkv import DKV

    key = "airline_dl_const.hex"
    out = recipe.device_columns(SEEDS[0], int(cfg["dry_run_rows"]),
                                sharding=cl.row_sharding())
    frame = _install(cl, key, out[:-1], out[-1])
    try:
        args = dict(response=recipe.RESPONSE_NAME,
                    use_all_factor_levels=bool(
                        cfg["params"]["use_all_factor_levels"]))
        di = DataInfo(frame, ignore_const_cols=True, **args)
        assert di.const_cols == () and di.fullN == 674
        assert di.layout() == DataInfo(frame, **args).layout()
    finally:
        DKV.remove(key)


def test_programs_hold_no_design_at_one_million_rows(cl, cfg):
    """The training program, the loss pass and the scoring pass of the
    configuration's network compiled at 1M rows: their temporaries stay
    under 64 B a row, where a (rows, 674) f32 design alone is 2,696 B a
    row (and the expanded form asked for it in every program)."""
    import jax

    rows = 1 << 20
    out = recipe.device_columns(SEEDS[0], rows, sharding=cl.row_sharding())
    fr = Frame()
    for (name, ctype, dom), c in zip(recipe.frame_columns(), out[:-1]):
        fr.add(name, Column.from_device(c, ctype, rows, domain=dom))
    di = DataInfo(fr, use_all_factor_levels=True)
    assert di.fullN == 674
    net = dl_mod._Net(di.layout(), "rectifier", 2, False, batch=32)
    params = dl_mod._init_params(di.fullN, [200, 200], 2, 1,
                                 "UniformAdaptive", 1.0)
    arrays = tuple(c.data for c in di.cols(fr))
    y = out[-1].astype(np.int32)
    w = jax.numpy.ones(rows, np.float32)
    state = dl_mod._optimizer(net).init(params)
    temps = {"train": dl_mod._dl_train_steps.lower(
        params, state, jax.random.PRNGKey(0), 10, rows, arrays, di.moments(),
        y, w, net=net).compile().memory_analysis().temp_size_in_bytes}
    mesh = cl.mesh
    for kind, extra in (("loss", (y, w)), ("predict", ())):
        prog = dl_mod._dl_pass(net, mesh, kind, 402)
        temps[kind] = prog.lower(params, di.moments(), arrays + extra) \
            .compile().memory_analysis().temp_size_in_bytes
    assert all(t < 64 * rows for t in temps.values()), temps


def test_draws_cover_the_real_rows_only(cl, cfg):
    """A frame whose rows do not tile the mesh has padding rows; the
    program's 64 steps draw from the real rows only, as the reference's
    replay on the same rows does (a draw over the padding would be another
    sequence of rows, and other weights)."""
    from h2o3_tpu.core.frame import T_CAT, T_NUM

    rows = int(cfg["dry_run_rows"]) - 5
    out = recipe.device_columns(SEEDS[1], rows + 5)
    host = [np.asarray(c)[:rows] for c in out]
    pad = cl.pad_rows(rows)
    assert pad > rows
    fr = Frame()
    for (name, ctype, dom), c in zip(recipe.frame_columns(), host[:-1]):
        if ctype == "enum":
            buf = np.full(pad, -1, c.dtype)
            buf[:rows] = c
            fr.add(name, Column(cl.put_rows(buf), T_CAT, rows, domain=dom))
        else:
            buf = np.full(pad, np.nan, np.float32)
            buf[:rows] = c
            fr.add(name, Column(cl.put_rows(buf), T_NUM, rows))
    ybuf = np.full(pad, -1, np.int8)
    ybuf[:rows] = host[-1]
    fr.add(recipe.RESPONSE_NAME, Column(cl.put_rows(ybuf), T_CAT, rows,
                                        domain=list(recipe.RESPONSE_DOMAIN)))
    import jax.numpy as jnp

    steps = 64
    batch = cfg["params"]["mini_batch_size"]
    dcols = [jnp.asarray(c) for c in host[:-1]]
    seed = dl_enum.clear_seed(dcols, jnp.asarray(host[-1]), cfg,
                              recipe.fold_seed(SEEDS[1])[0], steps)
    model = _train(cfg, fr, SEEDS[1], job_seed=seed,
                   epochs=steps * batch / rows)
    try:
        ref = dl_enum.replay(dcols, jnp.asarray(host[-1]), cfg, seed, steps)
        got = dl_reader.read_dl(_Sys(cl), str(model.key))["weights"]
        gap = dl_enum.weight_gap(got, ref, dl_enum.initial_weights(cfg, seed))
        assert gap < cfg["limits"]["weight_gap"], gap
    finally:
        model.delete()


def test_runs_of_the_training_program_carry_the_state(cl, cfg, short,
                                                      monkeypatch):
    """An epoch is split into runs of at most DL_STEPS_A_DISPATCH steps,
    the key and the optimizer's state carried from one run to the next:
    the mix's short job split into runs of 7 steps ends on the weights it
    ends on in whole epochs, bit for bit."""
    import jax

    from h2o3_tpu.core.dkv import DKV

    n, steps = int(short["rows"]), int(short["steps"])
    out = recipe.device_columns(SEEDS[2], n)
    first = [jax.device_put(c, cl.row_sharding()) for c in out]
    key = "airline_dl_runs.hex"
    frame = _install(cl, key, first[:-1], first[-1])
    epochs = steps * cfg["params"]["mini_batch_size"] / n
    whole = _train(cfg, frame, SEEDS[2], epochs=epochs)
    monkeypatch.setattr(dl_mod, "DL_STEPS_A_DISPATCH", 7)
    split = _train(cfg, frame, SEEDS[2], epochs=epochs)
    try:
        assert split.epochs_trained == whole.epochs_trained == 2.5
        for (Wa, ba), (Wb, bb) in zip(whole.params_tree, split.params_tree):
            assert np.array_equal(np.asarray(Wa), np.asarray(Wb))
            assert np.array_equal(np.asarray(ba), np.asarray(bb))
    finally:
        DKV.remove(key)
        whole.delete()
        split.delete()

"""DeepLearning tests (reference pyunits testdir_algos/deeplearning)."""

import numpy as np
import pytest

from h2o3_tpu.core.frame import Column, Frame, T_CAT


def _xor_data(n=2000, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1, 1, size=(n, 2))
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0)).astype(int)
    fr = Frame()
    fr.add("x0", Column.from_numpy(X[:, 0]))
    fr.add("x1", Column.from_numpy(X[:, 1]))
    fr.add("y", Column.from_numpy(np.where(y == 1, "on", "off"), ctype=T_CAT))
    return fr


def test_dl_learns_xor(cl):
    from h2o3_tpu.models.deeplearning import DeepLearning

    fr = _xor_data()
    m = DeepLearning(hidden=[16, 16], epochs=60, seed=42,
                     mini_batch_size=64).train(y="y", training_frame=fr)
    mm = m._output.training_metrics
    assert mm.auc > 0.97
    pred = m.predict(fr)
    assert set(pred.names) == {"predict", "off", "on"}


def test_dl_regression(cl):
    from h2o3_tpu.models.deeplearning import DeepLearning

    rng = np.random.default_rng(1)
    X = rng.normal(size=(3000, 3))
    y = np.sin(X[:, 0]) + X[:, 1] ** 2 + 0.1 * X[:, 2]
    fr = Frame.from_numpy(np.column_stack([X, y]), names=["a", "b", "c", "y"])
    m = DeepLearning(hidden=[32, 32], epochs=40, seed=0, activation="Tanh",
                     mini_batch_size=64).train(y="y", training_frame=fr)
    mm = m._output.training_metrics
    assert mm.r2 > 0.9
    vi = m.varimp()
    assert vi is not None and set(vi) == {"a", "b", "c"}


def test_dl_autoencoder_anomaly(cl):
    from h2o3_tpu.models.deeplearning import DeepLearning

    rng = np.random.default_rng(2)
    X = rng.normal(size=(2000, 4))
    X[:, 2] = X[:, 0] + 0.05 * rng.normal(size=2000)   # low-rank structure
    X[:, 3] = X[:, 1] - X[:, 0]
    fr = Frame.from_numpy(X, names=list("abcd"))
    m = DeepLearning(autoencoder=True, hidden=[2], epochs=40, seed=3,
                     activation="Tanh", mini_batch_size=64).train(training_frame=fr)
    # anomalous points reconstruct worse
    Xa = X.copy()
    Xa[:50] = rng.uniform(-6, 6, size=(50, 4))
    fra = Frame.from_numpy(Xa, names=list("abcd"))
    err = m.anomaly(fra).col("Reconstruction.MSE").to_numpy()
    assert err[:50].mean() > 3 * err[50:].mean()


def test_dl_sgd_momentum_path(cl):
    from h2o3_tpu.models.deeplearning import DeepLearning

    fr = _xor_data(n=1000, seed=5)
    m = DeepLearning(hidden=[16], epochs=40, seed=7, adaptive_rate=False,
                     rate=0.05, momentum_start=0.5, momentum_stable=0.9,
                     mini_batch_size=32).train(y="y", training_frame=fr)
    assert m._output.training_metrics.auc > 0.9


def test_dl_deepfeatures_shape(cl):
    from h2o3_tpu.models.deeplearning import DeepLearning

    fr = _xor_data(n=500, seed=6)
    m = DeepLearning(hidden=[8, 4], epochs=5, seed=1,
                     mini_batch_size=32).train(y="y", training_frame=fr)
    df = m.deepfeatures(fr, 1)
    assert df.ncols == 4 and df.nrows == 500


def test_autoencoder_metrics_and_versioned_save(cl, tmp_path):
    """ModelMetricsAutoEncoder (reconstruction MSE) + versioned artifact
    header (Iced/AutoBuffer analog)."""
    import numpy as np

    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.models.deeplearning import DeepLearning
    from h2o3_tpu.models.model import Model

    rng = np.random.default_rng(4)
    X = rng.standard_normal((400, 5))
    fr = Frame.from_numpy(X, names=[f"x{i}" for i in range(5)])
    m = DeepLearning(autoencoder=True, hidden=[3], epochs=3,
                     seed=1).train(training_frame=fr)
    mm = m._output.training_metrics
    assert mm is not None and np.isfinite(mm.mse) and mm.mse > 0
    assert "reconstruction" in mm.description
    # versioned save round-trip + foreign-file rejection
    p = str(tmp_path / "ae.bin")
    m.save(p)
    with open(p, "rb") as f:
        assert f.read(8) == b"H2O3TPUM"
    re = Model.load(p)
    assert float(re._output.training_metrics.mse) == float(mm.mse)
    bad = str(tmp_path / "bad.bin")
    with open(bad, "wb") as f:
        f.write(b"garbage-not-a-model")
    import pytest

    with pytest.raises(ValueError, match="not an h2o3_tpu model"):
        Model.load(bad)


def test_dl_epochs_count_samples_not_whole_passes(cl):
    """H2O trains epochs x rows samples: round(epochs x rows / batch)
    steps, the last epoch partial, epochs_trained a float."""
    from h2o3_tpu.models.deeplearning import DeepLearning
    from h2o3_tpu.obs import metrics

    def steps_total():
        return sum(s["value"] for s in metrics.REGISTRY.get(
            "h2o3_dl_steps_total").snapshot()["samples"])

    fr = _xor_data(n=1000, seed=9)
    before = steps_total()
    m = DeepLearning(hidden=[8], epochs=2.5, seed=4,
                     mini_batch_size=32).train(y="y", training_frame=fr)
    assert steps_total() - before == round(2.5 * 1000 / 32)
    assert [h["epoch"] for h in m._output.scoring_history] == \
        [32 * 32 / 1000, 64 * 32 / 1000, 2.5]
    assert m.epochs_trained == 2.5
    short = DeepLearning(hidden=[8], epochs=0.3, seed=4,
                         mini_batch_size=32).train(y="y", training_frame=fr)
    assert [h["epoch"] for h in short._output.scoring_history] == [0.3]
    assert short.epochs_trained == 0.3
    m.delete()
    short.delete()


def test_dl_epoch_in_runs_of_the_training_program_is_the_same_bits(
        cl, monkeypatch):
    """An epoch's steps go to the device in runs of at most
    DL_STEPS_A_DISPATCH; the runs carry the state and the key, so the
    weights are the same to the bit however the steps are cut."""
    from h2o3_tpu.models import deeplearning as dl_mod
    from h2o3_tpu.models.deeplearning import DeepLearning

    fr = _xor_data(n=1000, seed=11)

    def fit():
        return DeepLearning(hidden=[8], epochs=1.5, seed=2,
                            mini_batch_size=32).train(y="y",
                                                      training_frame=fr)

    whole = fit()
    monkeypatch.setattr(dl_mod, "DL_STEPS_A_DISPATCH", 5)
    cut = fit()
    try:
        for (a, b), (c, d) in zip(whole.params_tree, cut.params_tree):
            assert np.array_equal(np.asarray(a), np.asarray(c))
            assert np.array_equal(np.asarray(b), np.asarray(d))
    finally:
        whole.delete()
        cut.delete()

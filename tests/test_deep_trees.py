"""Deep-tree device grower: depth>10 trains in the SAME one-dispatch
dense-frontier program.

Reference shape: hex/tree/DHistogram.java:33-44 level-wise growth at DRF's
default depth 20; VERDICT r3 #4 acceptance: depth-20 DRF with no per-level
host sync."""

import numpy as np
import pytest

from h2o3_tpu.core.frame import Column, Frame


def _data(n=2500, seed=9):
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    g = np.array(["a", "b", "c"])[rng.integers(0, 3, n)]
    logit = 1.4 * x1 - x2 + (g == "a") * 1.0
    y = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)), "Y", "N")
    fr = Frame()
    fr.add("x1", Column.from_numpy(x1))
    fr.add("x2", Column.from_numpy(x2))
    fr.add("g", Column.from_numpy(g, ctype="enum"))
    fr.add("y", Column.from_numpy(y, ctype="enum"))
    fr.add("yreg", Column.from_numpy(logit + 0.2 * rng.normal(size=n)))
    return fr


def test_depth20_drf_no_host_fallback(cl):
    """DRF at its default depth 20 trains in the device grower (there is
    no other)."""
    from h2o3_tpu.models.tree.drf import DRF

    fr = _data()
    m = DRF(ntrees=8, max_depth=20, seed=1).train(
        x=["x1", "x2", "g"], y="y", training_frame=fr)
    assert m._output.training_metrics.auc > 0.75
    pred = m.predict(fr)
    p = np.asarray(pred.col("Y").to_numpy())
    assert np.all((p >= 0) & (p <= 1))


def test_depth20_drf_multinomial_device(cl):
    from h2o3_tpu.models.tree.drf import DRF

    rng = np.random.default_rng(2)
    n = 1200
    x1, x2 = rng.normal(size=n), rng.normal(size=n)
    ym = np.array(["p", "q", "r"])[np.argmax(
        np.column_stack([x1, x2, -x1 - x2]) + rng.normal(0, .4, (n, 3)), 1)]
    fr = Frame()
    fr.add("x1", Column.from_numpy(x1))
    fr.add("x2", Column.from_numpy(x2))
    fr.add("ym", Column.from_numpy(ym, ctype="enum"))
    m = DRF(ntrees=5, max_depth=14, seed=3).train(
        x=["x1", "x2"], y="ym", training_frame=fr)
    acc = (np.asarray(m.predict(fr).col("predict").to_numpy())
           == np.asarray(fr.col("ym").to_numpy())).mean()
    assert acc > 0.7


def test_deep_gbm_beats_shallow_underfit(cl):
    """Depth-12 GBM on a deep interaction surface must at least match a
    depth-2 model — proves deep levels actually split on device."""
    from h2o3_tpu.models.tree.gbm import GBM

    fr = _data()
    deep = GBM(ntrees=10, max_depth=12, seed=1, learn_rate=0.3).train(
        x=["x1", "x2", "g"], y="yreg", training_frame=fr)
    shallow = GBM(ntrees=10, max_depth=1, seed=1, learn_rate=0.3).train(
        x=["x1", "x2", "g"], y="yreg", training_frame=fr)
    assert deep._output.training_metrics.rmse < \
        shallow._output.training_metrics.rmse


def test_frontier_cap_binds_gracefully(cl, monkeypatch):
    """With a tiny frontier cap the grower keeps the best-gain splits and
    still produces a working model (greedy-best under the width budget)."""
    from h2o3_tpu.models.tree import device_tree

    monkeypatch.setattr(device_tree, "DEFAULT_FRONTIER_CAP", 16)
    device_tree._grow_fn.cache_clear()
    device_tree._apply_fn.cache_clear()
    try:
        from h2o3_tpu.models.tree.gbm import GBM

        fr = _data(n=1200)
        m = GBM(ntrees=5, max_depth=8, seed=1).train(
            x=["x1", "x2", "g"], y="y", training_frame=fr)
        assert m._output.training_metrics.auc > 0.7
        widths = device_tree.level_widths(8)
        assert max(widths) == 16                   # cap actually bound
    finally:
        device_tree._grow_fn.cache_clear()
        device_tree._apply_fn.cache_clear()


def test_deep_mojo_and_genmodel_roundtrip(cl):
    """Deep forests survive the MOJO container and the standalone numpy
    scorer (global-slot leaf ids are part of the artifact contract)."""
    import h2o3_genmodel as gm

    from h2o3_tpu.models import mojo
    from h2o3_tpu.models.tree.drf import DRF

    fr = _data(n=1500)
    m = DRF(ntrees=6, max_depth=15, seed=5).train(
        x=["x1", "x2", "g"], y="y", training_frame=fr)
    loaded = mojo.read_mojo(mojo.export_mojo_bytes(m))
    p0 = np.asarray(m.predict(fr).col("Y").to_numpy())
    p1 = np.asarray(loaded.predict(fr).col("Y").to_numpy())
    np.testing.assert_allclose(p0, p1, atol=0, rtol=0)
    pred = gm.load_mojo(mojo.export_mojo_bytes(m))
    got = pred.score({"x1": fr.col("x1").to_numpy(),
                      "x2": fr.col("x2").to_numpy(),
                      "g": np.asarray(["a", "b", "c"], object)[
                          np.asarray(fr.col("g").to_numpy())]})
    np.testing.assert_allclose(np.asarray(got["Y"], float), p0,
                               atol=1e-5, rtol=1e-5)


def test_validation_scoring_deep(cl):
    """apply_packed (in-training validation traversal) works at depth>10."""
    from h2o3_tpu.models.tree.gbm import GBM

    fr = _data(n=2000)
    tr_rows = np.arange(1500)
    va_rows = np.arange(1500, 2000)

    def subset(rows):
        out = Frame()
        for nm in fr.names:
            c = fr.col(nm)
            out.add(nm, Column.from_numpy(
                np.asarray(c.to_numpy())[rows], ctype="enum" if c.domain else None,
                domain=list(c.domain) if c.domain else None))
        return out

    tr, va = subset(tr_rows), subset(va_rows)
    m = GBM(ntrees=8, max_depth=12, seed=1,
            score_each_iteration=True).train(
        x=["x1", "x2", "g"], y="y", training_frame=tr, validation_frame=va)
    hist = m._output.scoring_history
    assert any("validation_deviance" in h for h in hist)

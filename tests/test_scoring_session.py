"""Serving fast path (scoring.py): shape-bucketed fused scoring sessions.

Covers the ISSUE-2 acceptance bar: scoring requests with distinct row
counts against one trained GBM compiles at most len(buckets) traversal
programs (asserted with JAX's compilation counters), and padded rows never
leak — the bucketed path returns BITWISE-identical predictions to the
per-request unbatched path."""

import numpy as np
import pytest

from h2o3_tpu.core.frame import Column, Frame


def _train_frame(n=1500, seed=0, classes=2):
    rng = np.random.default_rng(seed)
    fr = Frame()
    x1 = rng.standard_normal(n)
    x2 = rng.standard_normal(n)
    g = np.array(["a", "b", "c"])[rng.integers(0, 3, n)]
    fr.add("x1", Column.from_numpy(x1))
    fr.add("x2", Column.from_numpy(x2))
    fr.add("g", Column.from_numpy(g, ctype="enum"))
    logit = 1.2 * x1 - x2 + (g == "a") * 0.5
    if classes == 2:
        y = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)), "Y", "N")
    else:
        y = np.array(["r", "s", "t"])[
            np.clip((logit + rng.normal(0, 0.5, n) + 1.5).astype(int), 0,
                    classes - 1)]
    fr.add("y", Column.from_numpy(y, ctype="enum"))
    return fr


def _score_frame(n, seed, with_nas=False):
    rng = np.random.default_rng(seed)
    fr = Frame()
    x1 = rng.standard_normal(n)
    x2 = rng.standard_normal(n)
    if with_nas:
        x1[:: 7] = np.nan
    fr.add("x1", Column.from_numpy(x1))
    fr.add("x2", Column.from_numpy(x2))
    fr.add("g", Column.from_numpy(
        np.array(["a", "b", "c"])[rng.integers(0, 3, n)], ctype="enum"))
    return fr


@pytest.fixture(scope="module")
def gbm(cl):
    from h2o3_tpu.models.tree.gbm import GBM

    return GBM(ntrees=8, max_depth=3, seed=1).train(
        y="y", training_frame=_train_frame())


def _assert_frames_bitwise(a, b, n):
    assert a.names == b.names
    for name in a.names:
        av = np.asarray(a.col(name).data)[:n]
        bv = np.asarray(b.col(name).data)[:n]
        assert np.array_equal(av, bv), (name, av[:5], bv[:5])


class TestCompileStability:
    SIZES = (17, 300, 1000, 4096, 9999)

    def test_at_most_len_buckets_traversal_traces(self, cl, gbm):
        """5 distinct request row counts → ≤ len(buckets) compiled
        programs, counted on the repo's compile ledger (every XLA compile
        on the bucketed dispatch records one row there)."""
        from h2o3_tpu import scoring
        from h2o3_tpu.obs import compiles

        def compiled_programs():
            return sum(a["compiles"]
                       for a in compiles.family_table().values())

        sess = scoring.ScoringSession(gbm)      # fresh: nothing traced yet
        feats = {n: sess._features(gbm.adapt_test(_score_frame(n, n)), n)
                 for n in self.SIZES}
        before = compiled_programs()
        margins = {n: sess._margin_x(feats[n]) for n in self.SIZES}
        new = compiled_programs() - before
        assert 1 <= new <= len(sess.buckets), (new, sess.buckets)
        assert sess.traversal_compiles <= len(sess.buckets)
        # margins are exact vs the unbatched binned traversal
        for n, mg in margins.items():
            ref = np.asarray(gbm._margin(gbm.adapt_test(_score_frame(n, n))))
            assert np.array_equal(mg[:n], ref[:n]), n

        # NEW row counts that land in warm buckets compile nothing — the
        # per-request-shape jit cost is gone entirely
        feats2 = {n: sess._features(gbm.adapt_test(_score_frame(n, 99 + n)),
                                    n) for n in (60, 900, 2222)}
        before = compiled_programs()
        for n, x in feats2.items():
            sess._margin_x(x)
        assert compiled_programs() == before

    def test_padded_rows_never_leak(self, cl, gbm):
        """Bucket padding must be invisible: bucketed predictions are
        bitwise-identical to the per-request unbatched path, including
        frames with NAs."""
        from h2o3_tpu import scoring

        sess = scoring.session_for(gbm)
        for n in self.SIZES:
            fr = _score_frame(n, n, with_nas=True)
            _assert_frames_bitwise(gbm.predict(fr), sess.predict(fr), n)


class TestBucketConfig:
    def test_env_buckets_and_chunking(self, cl, gbm, monkeypatch):
        """H2O_TPU_SCORE_BUCKETS overrides the ladder; requests above the
        largest bucket chunk at it instead of compiling new shapes."""
        from h2o3_tpu import scoring

        monkeypatch.setenv("H2O_TPU_SCORE_BUCKETS", "64,256")
        sess = scoring.ScoringSession(gbm)
        assert sess.buckets == (64, 256)
        fr = _score_frame(700, 5)     # 700 > 256 → 3 chunks of ≤256
        _assert_frames_bitwise(gbm.predict(fr), sess.predict(fr), 700)
        assert sess.traversal_compiles <= 2

    def test_bad_env_falls_back(self, cl, monkeypatch):
        from h2o3_tpu import scoring

        monkeypatch.setenv("H2O_TPU_SCORE_BUCKETS", "nope")
        assert scoring._env_buckets() == scoring._DEFAULT_BUCKETS


class TestModelFamilies:
    def test_multinomial_bitwise(self, cl):
        from h2o3_tpu import scoring
        from h2o3_tpu.models.tree.gbm import GBM

        m = GBM(ntrees=4, max_depth=3, seed=2).train(
            y="y", training_frame=_train_frame(seed=3, classes=3))
        assert scoring.supports(m)
        sess = scoring.session_for(m)
        fr = _score_frame(333, 11)
        _assert_frames_bitwise(m.predict(fr), sess.predict(fr), 333)

    def test_regression_bitwise(self, cl):
        from h2o3_tpu import scoring
        from h2o3_tpu.models.tree.gbm import GBM

        rng = np.random.default_rng(4)
        n = 1200
        fr = Frame()
        x = rng.standard_normal(n)
        fr.add("x1", Column.from_numpy(x))
        fr.add("x2", Column.from_numpy(rng.standard_normal(n)))
        fr.add("g", Column.from_numpy(
            np.array(["a", "b"])[rng.integers(0, 2, n)], ctype="enum"))
        fr.add("y", Column.from_numpy(2 * x + rng.normal(0, 0.1, n)))
        m = GBM(ntrees=5, max_depth=3, seed=2).train(y="y",
                                                     training_frame=fr)
        sess = scoring.session_for(m)
        tf = _score_frame(97, 7)
        _assert_frames_bitwise(m.predict(tf), sess.predict(tf), 97)

    def test_drf_supported_isofor_not(self, cl, gbm):
        from h2o3_tpu import scoring
        from h2o3_tpu.models.tree.drf import DRF
        from h2o3_tpu.models.tree.isofor import IsolationForest

        drf = DRF(ntrees=4, max_depth=4, seed=5).train(
            y="y", training_frame=_train_frame(seed=6))
        assert scoring.supports(drf)
        fr = _score_frame(150, 8)
        _assert_frames_bitwise(drf.predict(fr),
                               scoring.session_for(drf).predict(fr), 150)
        isf = IsolationForest(ntrees=4, max_depth=4, seed=5).train(
            training_frame=_score_frame(300, 9))
        # IsolationForest overrides _predict_raw (mean_length output) →
        # generic path, fast path refuses it
        assert not scoring.supports(isf)

    def test_kill_switch(self, cl, gbm, monkeypatch):
        from h2o3_tpu import scoring

        monkeypatch.setenv("H2O_TPU_SCORE_FAST", "0")
        assert not scoring.supports(gbm)


class _NoMeshCluster:
    """Cluster proxy whose global-mesh entry points trip an assertion:
    degraded-cloud local dispatch must never reach them (a sharded
    device_put / put_rows against the global mesh is an SPMD program a
    dead follower never joins)."""

    def __init__(self, cl):
        self._real = cl

    def pad_rows(self, n):                   # pure arithmetic: allowed
        return self._real.pad_rows(n)

    def row_sharding(self):
        raise AssertionError("local dispatch touched the global mesh "
                             "(row_sharding)")

    def put_rows(self, buf):
        raise AssertionError("local dispatch touched the global mesh "
                             "(put_rows)")


class TestDegradedLocalDispatch:
    def test_local_dispatch_never_touches_global_mesh(self, cl, gbm):
        """`local=True` (degraded-cloud serving) computes margins and raw
        predictions entirely on this process's devices — and stays
        bitwise-identical to the normal bucketed path."""
        from h2o3_tpu import scoring

        sess = scoring.ScoringSession(gbm)
        n = 300
        fr = _score_frame(n, 5, with_nas=True)
        X = sess._features(gbm.adapt_test(fr), n)
        ref_margin = sess._margin_x(X)
        ref_raw = sess._raw_for_slice(ref_margin, n)

        sess._cl = _NoMeshCluster(sess._cl)
        local_margin = sess._margin_x(X, local=True)
        assert np.array_equal(local_margin, ref_margin)
        raw = sess._raw_for_slice(local_margin, n, local=True)
        for k, ref in ref_raw.items():
            assert np.array_equal(np.asarray(raw[k])[:n],
                                  np.asarray(ref)[:n]), k

    def test_local_arrays_guard_non_addressable_model(self, cl, gbm):
        """Forest arrays the coordinator cannot fully read (shards homed on
        the dead peer) must refuse local serving with a clear error, not
        crash inside a host transfer."""
        from h2o3_tpu import scoring
        from h2o3_tpu.core.failure import CloudUnhealthyError

        sess = scoring.ScoringSession(gbm)

        class _Remote:                 # quacks like a non-addressable array
            is_fully_addressable = False

        sess._arrays = (_Remote(),)
        with pytest.raises(CloudUnhealthyError, match="forest arrays"):
            sess._local_arrays()


class TestSessionRegistry:
    def test_reuse_and_purge(self, cl, gbm):
        from h2o3_tpu import scoring

        s1 = scoring.session_for(gbm)
        assert scoring.session_for(gbm) is s1
        scoring.purge(str(gbm.key))
        assert scoring.session_for(gbm) is not s1

    def test_metrics_snapshot_shape(self, cl, gbm):
        from h2o3_tpu import scoring

        sess = scoring.session_for(gbm)
        sess.predict(_score_frame(40, 12))
        snap = [e for e in scoring.metrics_snapshot()
                if e["model"] == str(gbm.key)]
        assert snap and snap[0]["requests"] >= 1
        assert "p50_ms" in snap[0] and snap[0]["buckets"] == list(sess.buckets)

"""Sharded data plane (ISSUE 7): per-process feature packing over
addressable row shards + shard_map fused scoring.

Run on the conftest's virtual 8-device CPU mesh (single process, ≥2
devices — the proof platform the issue names; gloo 2-process clouds abort
in this environment). Covers:

- ShardedFrame packing is bitwise-identical to the host-packed matrix and
  keeps the named-row-axis sharding (no coordinator column staging).
- Sharded fused predictions are bitwise-identical to the host-packed path
  AND the generic predict path, including chunked (> max bucket) requests
  and multinomial forests.
- data-plane counters: packed_rows covers every sharded-path row,
  gathered_rows stays 0 on the sharded path and increments only on the
  host-gather fallbacks; surfaced on GET /3/ScoringMetrics.
- degraded-mode serving (satellite): coordinator-addressable sharded
  frames SERVE under local_only on a simulated multi-process cloud; the
  two ShardUnavailableError sites (non-addressable frame columns,
  non-addressable forest arrays) stay the exceptional path.
"""

import numpy as np
import pytest

from h2o3_tpu.core.frame import Column, Frame

pytestmark = pytest.mark.sharded


def _train_frame(n=1500, seed=0, classes=2):
    rng = np.random.default_rng(seed)
    fr = Frame()
    x1 = rng.standard_normal(n)
    x2 = rng.standard_normal(n)
    x1[::11] = np.nan
    g = np.array(["a", "b", "c"])[rng.integers(0, 3, n)]
    fr.add("x1", Column.from_numpy(x1))
    fr.add("x2", Column.from_numpy(x2))
    fr.add("g", Column.from_numpy(g, ctype="enum"))
    logit = np.where(np.isnan(x1), 0.0, 1.2 * x1) - x2 + (g == "a") * 0.5
    if classes == 2:
        y = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)), "Y", "N")
    else:
        y = np.array(["r", "s", "t"])[
            np.clip((logit + 1.5).astype(int), 0, classes - 1)]
    fr.add("y", Column.from_numpy(y, ctype="enum"))
    return fr


def _score_frame(n, seed, with_nas=True, unseen=False):
    rng = np.random.default_rng(seed)
    fr = Frame()
    x1 = rng.standard_normal(n)
    if with_nas:
        x1[::7] = np.nan
    fr.add("x1", Column.from_numpy(x1))
    fr.add("x2", Column.from_numpy(rng.standard_normal(n)))
    dom = ["a", "b", "c", "zz"] if unseen else ["a", "b", "c"]
    fr.add("g", Column.from_numpy(
        np.array(dom)[rng.integers(0, len(dom), n)], ctype="enum"))
    return fr


@pytest.fixture(scope="module")
def gbm(cl):
    from h2o3_tpu.models.tree.gbm import GBM

    return GBM(ntrees=6, max_depth=3, seed=1).train(
        y="y", training_frame=_train_frame())


@pytest.fixture(scope="module")
def gbm3(cl):
    from h2o3_tpu.models.tree.gbm import GBM

    return GBM(ntrees=4, max_depth=3, seed=2).train(
        y="y", training_frame=_train_frame(seed=3, classes=3))


def _counters():
    from h2o3_tpu.core import sharded_frame

    return sharded_frame.counters()


def _assert_frames_bitwise(a, b, n):
    assert a.names == b.names
    for name in a.names:
        av = np.asarray(a.col(name).data)[:n]
        bv = np.asarray(b.col(name).data)[:n]
        assert np.array_equal(av, bv, equal_nan=True), name


class TestShardedView:
    def test_view_holds_and_names_row_axis(self, cl, gbm):
        fr = _score_frame(300, 4)
        sf = fr.sharded_view()
        assert sf is not None
        assert sf.row_axis == "rows"
        assert sf.padded_rows % cl.row_shards == 0
        from jax.sharding import NamedSharding

        assert isinstance(sf.row_sharding(), NamedSharding)

    def test_view_refuses_host_resident_columns(self, cl):
        fr = Frame()
        fr.add("s", Column.from_numpy(np.array(["u", "v", "w"], object)))
        assert fr.sharded_view() is None

    def test_view_respects_plane_switch(self, cl, monkeypatch):
        fr = _score_frame(100, 5)
        monkeypatch.setenv("H2O_TPU_SHARDED_PLANE", "0")
        assert fr.sharded_view() is None
        monkeypatch.delenv("H2O_TPU_SHARDED_PLANE")
        assert fr.sharded_view() is not None

    def test_dkv_resolved_view(self, cl):
        from h2o3_tpu.core.sharded_frame import ShardedFrame

        fr = _score_frame(64, 6)
        fr._key = type(fr._key)("sharded_view_dkv.hex")
        fr.install()
        try:
            sf = ShardedFrame.for_key("sharded_view_dkv.hex")
            assert sf is not None and sf.frame is fr
            assert ShardedFrame.for_key("never_installed.hex") is None
        finally:
            fr.delete()

    def test_pack_features_matches_host_matrix(self, cl, gbm):
        from h2o3_tpu import scoring

        fr = _score_frame(333, 7, unseen=True)
        sess = scoring.ScoringSession(gbm)
        adapted = gbm.adapt_test(fr)
        sf = sess._sharded_view(adapted)
        assert sf is not None
        bucket = sess._bucket_for(fr.nrows)
        Xd = np.asarray(sf.pack_features(0, fr.nrows, bucket))
        Xh = sess._features(adapted, fr.nrows)
        assert np.array_equal(Xd[: fr.nrows], Xh, equal_nan=True)
        assert not np.isnan(Xd[fr.nrows:]).any()
        assert (Xd[fr.nrows:] == 0).all()      # zero pad, like the host path


class TestBinnedPack:
    def test_binned_pack_matches_legacy_and_stays_sharded(self, cl, gbm,
                                                          monkeypatch):
        fr = _score_frame(500, 8)
        adapted = gbm.adapt_test(fr)
        binned_sharded = gbm.spec.bin_columns(adapted)
        from jax.sharding import NamedSharding

        assert isinstance(binned_sharded.sharding, NamedSharding)
        spec_names = {ax for ax in (binned_sharded.sharding.spec or ())
                      if ax is not None}
        assert "rows" in spec_names
        monkeypatch.setenv("H2O_TPU_SHARDED_PLANE", "0")
        binned_legacy = gbm.spec.bin_columns(adapted)
        assert np.array_equal(np.asarray(binned_sharded),
                              np.asarray(binned_legacy))
        assert binned_sharded.dtype == binned_legacy.dtype

    def test_training_counts_packed_rows(self, cl):
        from h2o3_tpu.models.tree.gbm import GBM

        before = _counters()
        GBM(ntrees=2, max_depth=2, seed=9).train(
            y="y", training_frame=_train_frame(n=400, seed=10))
        after = _counters()
        assert after["packed_rows"] > before["packed_rows"]
        assert after["gathered_rows"] == before["gathered_rows"]


class TestShardedScoring:
    def _ab(self, model, fr, monkeypatch=None, buckets=None):
        """Score `fr` through the sharded plane and the host-packed path
        (plane off) with fresh sessions; return both prediction frames."""
        import os

        from h2o3_tpu import scoring

        if buckets:
            os.environ["H2O_TPU_SCORE_BUCKETS"] = buckets
        try:
            pred_s = scoring.ScoringSession(model).predict(fr)
            os.environ["H2O_TPU_SHARDED_PLANE"] = "0"
            try:
                pred_h = scoring.ScoringSession(model).predict(fr)
            finally:
                del os.environ["H2O_TPU_SHARDED_PLANE"]
        finally:
            if buckets:
                del os.environ["H2O_TPU_SCORE_BUCKETS"]
        return pred_s, pred_h

    def test_binomial_bitwise_vs_host_path(self, cl, gbm):
        fr = _score_frame(777, 11, unseen=True)
        before = _counters()
        pred_s, pred_h = self._ab(gbm, fr)
        after = _counters()
        _assert_frames_bitwise(pred_s, pred_h, fr.nrows)
        # sharded run packed its rows without a gather; the host-path
        # run is the one that gathered
        assert after["packed_rows"] - before["packed_rows"] == fr.nrows
        assert after["gathered_rows"] - before["gathered_rows"] == fr.nrows

    def test_binomial_bitwise_vs_generic_path(self, cl, gbm):
        from h2o3_tpu import scoring

        fr = _score_frame(420, 12)
        pred_s = scoring.ScoringSession(gbm).predict(fr)
        pred_g = gbm.predict(fr)
        for name in pred_s.names:
            assert np.array_equal(
                np.asarray(pred_s.col(name).data)[: fr.nrows],
                np.asarray(pred_g.col(name).data)[: fr.nrows],
                equal_nan=True), name

    def test_multinomial_bitwise(self, cl, gbm3):
        fr = _score_frame(513, 13)
        pred_s, pred_h = self._ab(gbm3, fr)
        _assert_frames_bitwise(pred_s, pred_h, fr.nrows)

    def test_chunked_request_bitwise(self, cl, gbm):
        """Requests above the largest bucket chunk at it on BOTH paths;
        the sharded assembly (concat + reshard) stays bitwise."""
        fr = _score_frame(1000, 14)
        pred_s, pred_h = self._ab(gbm, fr, buckets="256")
        _assert_frames_bitwise(pred_s, pred_h, fr.nrows)

    def test_compiles_bounded_by_buckets(self, cl, gbm):
        from h2o3_tpu import scoring

        sess = scoring.ScoringSession(gbm)
        for n, seed in ((100, 20), (300, 21), (900, 22), (1100, 23),
                        (140, 24)):
            sess.predict(_score_frame(n, seed))
        assert sess.traversal_compiles <= len(sess.buckets)

    def test_batch_mixes_sharded_and_fallback_entries(self, cl, gbm):
        """One coalesced batch where an entry is sharded-eligible and
        another carries a padded layout the view refuses — results stay
        per-entry correct and in order."""
        from h2o3_tpu import scoring

        fr_ok = _score_frame(200, 25)
        fr_ragged = _score_frame(150, 26)
        fr_clean = _score_frame(150, 26)     # same values, legal layout
        # forcing one column's padded length out of agreement makes the
        # view refuse (ragged layout) without touching the logical values
        import jax.numpy as jnp

        c = fr_ragged.col("x2")
        longer = jnp.pad(c.data, (0, cl.pad_rows(c.data.shape[0] + 1)
                                  - c.data.shape[0]), constant_values=np.nan)
        c.data = longer
        assert fr_ragged.sharded_view() is None
        sess = scoring.ScoringSession(gbm)
        before = _counters()
        out = sess.predict_batch([(fr_ok, None, False),
                                  (fr_ragged, None, False)])
        after = _counters()
        assert len(out) == 2
        # first entry packed shard-locally; the ragged one fell back to
        # the host-gather path
        assert after["packed_rows"] - before["packed_rows"] == fr_ok.nrows
        assert after["gathered_rows"] - before["gathered_rows"] == \
            fr_ragged.nrows
        for fr, ref_fr, (pred, _mm) in zip(
                (fr_ok, fr_ragged), (fr_ok, fr_clean), out):
            ref = gbm.predict(ref_fr)
            for name in ref.names:
                assert np.array_equal(
                    np.asarray(pred.col(name).data)[: fr.nrows],
                    np.asarray(ref.col(name).data)[: fr.nrows],
                    equal_nan=True), name


class _NonAddressable:
    """Stand-in for a device array whose shards live on a dead peer."""

    is_fully_addressable = False
    shape = (64,)

    @property
    def sharding(self):            # _shard_owners introspection: best-effort
        raise RuntimeError("no sharding: peer is gone")


class TestDegradedServing:
    """Satellite: degraded-mode serving on sharded frames. Addressable
    shards SERVE; the two ShardUnavailableError sites in scoring.py are
    the exceptional path (one test per branch)."""

    def test_local_only_serves_addressable_sharded_frame(self, cl, gbm,
                                                         monkeypatch):
        """Simulated multi-process degraded cloud (process_count > 1,
        local_only): a frame whose shards are all coordinator-addressable
        must serve — via the host-packed LOCAL dispatch, never the global
        mesh — with predictions bitwise-identical to the healthy path."""
        import jax

        from h2o3_tpu import scoring

        fr = _score_frame(210, 30)
        healthy = scoring.ScoringSession(gbm).predict(fr)
        sess = scoring.ScoringSession(gbm)
        before = _counters()
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        try:
            (pred, _mm), = sess.predict_batch([(fr, None, False)],
                                              local_only=True)
        finally:
            monkeypatch.undo()
        after = _counters()
        _assert_frames_bitwise(pred, healthy, fr.nrows)
        # degraded-local serving is the documented host-gather fallback
        assert after["gathered_rows"] - before["gathered_rows"] == fr.nrows

    def test_local_only_unaddressable_frame_raises(self, cl, gbm,
                                                   monkeypatch):
        """scoring.predict_batch's frame-shard check: a column homed on a
        dead peer refuses with ShardUnavailableError (503 surface)."""
        import jax

        from h2o3_tpu import scoring
        from h2o3_tpu.core.failure import ShardUnavailableError

        fr = _score_frame(100, 31)
        fr.col("x2")._data = _NonAddressable()
        sess = scoring.ScoringSession(gbm)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        with pytest.raises(ShardUnavailableError) as ei:
            sess.predict_batch([(fr, None, False)], local_only=True)
        assert "x2" in str(ei.value)

    def test_local_only_unaddressable_forest_raises(self, cl, gbm,
                                                    monkeypatch):
        """scoring._local_arrays' forest-shard check: model arrays laid
        out over the global mesh with a dead owner refuse with
        ShardUnavailableError instead of entering a doomed collective."""
        from h2o3_tpu import scoring
        from h2o3_tpu.core.failure import ShardUnavailableError

        sess = scoring.ScoringSession(gbm)
        sess._arrays = (_NonAddressable(),) + tuple(sess._arrays[1:])
        sess._local_cache = None
        with pytest.raises(ShardUnavailableError):
            sess._local_arrays()


class TestScoringMetricsRest:
    def test_data_plane_counters_on_rest(self, cl, gbm):
        """GET /3/ScoringMetrics carries the per-process data_plane block;
        after a REST-scored sharded request, gathered_rows has not moved
        and packed_rows covers the scored frame (the issue's counter
        assertion, over the real wire).

        ISSUE-8 extension, same request: (a) the response's trace id
        resolves on GET /3/Trace/{id} to the COMPLETE fused-path span
        tree — ingress -> queue_wait, flush -> adapt, pack, dispatch,
        fetch, metrics — and
        the unchanged gathered_rows / fused-compile counters are the
        proof that tracing added no device sync or path change; (b)
        GET /3/Metrics serves the cluster-aggregated
        h2o3_data_plane_* series in Prometheus text exposition with the
        same values the data_plane block reports."""
        import json
        import re
        import urllib.request

        from h2o3_tpu import scoring
        from h2o3_tpu.api.server import start_server
        from h2o3_tpu.core import sharded_frame

        fr = _score_frame(160, 32)
        fr._key = type(fr._key)("sharded_metrics.hex")
        fr.install()
        srv = start_server(port=0)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            # warm the session so the traced request compiles nothing (the
            # no-new-compiles assertion below needs a warm bucket)
            scoring.session_for(gbm).predict(fr)
            compiles0 = scoring.session_for(gbm).fused_compiles
            before = sharded_frame.counters()
            req = urllib.request.Request(
                base + f"/3/Predictions/models/{gbm.key}/frames/"
                f"{fr.key}?predictions_frame=sharded_metrics_pred",
                data=b"", method="POST")
            with urllib.request.urlopen(req, timeout=120) as r:
                trace_id = r.headers.get("X-H2O3-Trace-Id")
                json.loads(r.read())
            with urllib.request.urlopen(base + "/3/ScoringMetrics",
                                        timeout=30) as r:
                sm = json.loads(r.read())
            dp = sm["data_plane"]
            assert dp["gathered_rows"] == before["gathered_rows"]
            assert dp["packed_rows"] >= before["packed_rows"] + fr.nrows
            # -- span tree (ISSUE 8 acceptance): complete fused-path
            #    phases, and zero new fused compiles / gathers while
            #    traced (tracing must not change the dispatch path)
            assert trace_id
            assert scoring.session_for(gbm).fused_compiles == compiles0
            with urllib.request.urlopen(base + f"/3/Trace/{trace_id}",
                                        timeout=30) as r:
                tr = json.loads(r.read())
            names = {s["name"] for s in tr["spans"]}
            assert {"ingress", "queue_wait", "flush", "adapt", "pack",
                    "dispatch", "fetch", "metrics"} <= names, names
            roots = tr["tree"]
            assert roots[0]["name"] == "ingress"
            children = {c["name"]: c for c in roots[0]["children"]}
            assert {"queue_wait", "flush"} <= set(children)
            phases = {c["name"]: c for c in children["flush"]["children"]}
            assert {"adapt", "windows", "fetch", "metrics"} <= set(phases)
            # one entry: the window loop packs and dispatches (ISSUE 36)
            assert {"pack", "dispatch"} <= {
                c["name"] for c in phases["windows"]["children"]}
            # -- cluster /3/Metrics agrees with the data_plane block
            with urllib.request.urlopen(base + "/3/Metrics",
                                        timeout=30) as r:
                text = r.read().decode()
            m = re.search(r"^h2o3_data_plane_packed_rows_total (\S+)$",
                          text, re.M)
            assert m and float(m.group(1)) == dp["packed_rows"]
            m = re.search(r"^h2o3_data_plane_gathered_rows_total (\S+)$",
                          text, re.M)
            assert m and float(m.group(1)) == dp["gathered_rows"]
            series = {ln.split("{")[0].split(" ")[0]
                      for ln in text.splitlines()
                      if ln.strip() and not ln.startswith("#")}
            assert len(series) >= 20
        finally:
            srv.stop()
            fr.delete()

"""Serving at production QPS (ISSUE 13).

- **Coalesced flush**: a multi-entry micro-batch flush on the sharded
  path costs ONE fused dispatch per row bucket (device-side concat of
  per-entry shard-packed matrices; the recorded PR-7 per-entry-dispatch
  trade-off is gone), bitwise-identical to per-entry scoring, with
  ``gathered_rows`` still 0.
- **Fused explainability**: leaf assignment and staged probabilities run
  through the ScoringSession's fused bucketed bin+leaf programs and stay
  bitwise-identical to the eager ``bin_columns + leaf_index`` path; the
  ``/4`` async route rides the fused coalescing path and matches the
  eager predict bitwise over real HTTP (contributions likewise).
- **SLO-adaptive admission**: ``H2O_TPU_SCORE_SLO_MS`` derives per-model
  inflight limits from the observed latency ring (AIMD), sheds with 429 +
  drain-rate-derived Retry-After, and the saturation soak (slow marker)
  holds p99 within the SLO with ZERO fused recompiles
  (compile-ledger-asserted).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from h2o3_tpu.core.frame import Column, Frame

pytestmark = pytest.mark.serving


def _train_frame(n=1500, seed=0):
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
    y = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)), "Y", "N")
    fr.add("y", Column.from_numpy(y, ctype="enum"))
    return fr


def _score_frame(n, seed, with_nas=True):
    rng = np.random.default_rng(seed)
    fr = Frame()
    x1 = rng.standard_normal(n)
    if with_nas:
        x1[::7] = np.nan
    fr.add("x1", Column.from_numpy(x1))
    fr.add("x2", Column.from_numpy(rng.standard_normal(n)))
    fr.add("g", Column.from_numpy(
        np.array(["a", "b", "c"])[rng.integers(0, 3, n)], ctype="enum"))
    return fr


@pytest.fixture(scope="module")
def gbm(cl):
    from h2o3_tpu.models.tree.gbm import GBM

    return GBM(ntrees=6, max_depth=3, seed=1).train(
        y="y", training_frame=_train_frame())


def _assert_frames_bitwise(a, b, n):
    assert a.names == b.names
    for name in a.names:
        av = np.asarray(a.col(name).data)[:n]
        bv = np.asarray(b.col(name).data)[:n]
        assert np.array_equal(av, bv, equal_nan=True), name


# ---------------------------------------------------------------------------
# coalesced flush: one window loop, each entry windowed on its own rows
# ---------------------------------------------------------------------------

class TestCoalescedFlush:
    def test_multi_entry_flush_costs_one_dispatch_an_entry(self, cl, gbm):
        """5 sharded-eligible entries, each under one bucket → one fused
        dispatch an entry, per-entry results bitwise-identical to
        individual predicts, gathered_rows untouched."""
        from h2o3_tpu import scoring
        from h2o3_tpu.core import sharded_frame

        frames = [_score_frame(60 + 37 * i, 40 + i) for i in range(5)]
        refs = [gbm.predict(fr) for fr in frames]
        sess = scoring.session_for(gbm)
        for fr in frames:
            sess.predict(fr)               # warm the buckets involved
        before_dp = sharded_frame.counters()
        scoring.reset_dispatch_counters()
        out = sess.predict_batch([(fr, None, False) for fr in frames])
        dc = scoring.dispatch_counters()
        after_dp = sharded_frame.counters()
        assert dc.get("sharded") == 5, dc
        assert "host" not in dc and "local" not in dc
        assert after_dp["gathered_rows"] == before_dp["gathered_rows"]
        for fr, ref, (pred, _mm) in zip(frames, refs, out):
            _assert_frames_bitwise(ref, pred, fr.nrows)

    def test_coalesced_flush_chunks_at_bucket_ladder(self, cl, gbm,
                                                     monkeypatch):
        """Entries over the largest bucket chunk at it, each on its own
        rows — dispatches == sum(ceil(n_i / maxb)) — and every entry's
        slice stays bitwise."""
        import os

        from h2o3_tpu import scoring

        os.environ["H2O_TPU_SCORE_BUCKETS"] = "256"
        try:
            sess = scoring.ScoringSession(gbm)
            frames = [_score_frame(300, 50 + i) for i in range(3)]
            refs = [gbm.predict(fr) for fr in frames]
            sess.predict(frames[0])        # warm the single bucket
            scoring.reset_dispatch_counters()
            out = sess.predict_batch([(fr, None, False) for fr in frames])
            dc = scoring.dispatch_counters()
            # 300 rows over 256-row buckets → 2 windows an entry
            assert dc.get("sharded") == 6, dc
            for fr, ref, (pred, _mm) in zip(frames, refs, out):
                _assert_frames_bitwise(ref, pred, fr.nrows)
        finally:
            del os.environ["H2O_TPU_SCORE_BUCKETS"]

    def test_dispatch_accounting_surfaces(self, cl, gbm):
        """Per-model dispatches land in the session stats and the
        process-wide counters feed h2o3_score_dispatches_total; the flush
        histogram records the batch width."""
        from h2o3_tpu import scoring
        from h2o3_tpu.obs import metrics as obs_metrics

        sess = scoring.session_for(gbm)
        frames = [_score_frame(64, 70 + i) for i in range(3)]
        sess.predict_batch([(fr, None, False) for fr in frames])
        snap = [e for e in scoring.metrics_snapshot()
                if e["model"] == str(gbm.key)][0]
        assert snap["dispatches"] >= 1
        assert "dispatches_per_flush" in snap
        m = obs_metrics.REGISTRY.get("h2o3_score_dispatches_total")
        samples = m.snapshot()["samples"]
        assert any(s["labels"].get("path") == "sharded" and s["value"] >= 1
                   for s in samples), samples
        h = obs_metrics.REGISTRY.get("h2o3_score_flush_requests")
        hs = h.snapshot()["samples"]
        assert hs and hs[0]["count"] >= 1


# ---------------------------------------------------------------------------
# fused explainability outputs
# ---------------------------------------------------------------------------

class TestFusedExplainability:
    def test_leaf_matrix_bitwise_vs_eager(self, cl, gbm):
        from h2o3_tpu import scoring

        fr = _score_frame(333, 80)
        adapted = gbm.adapt_test(fr)
        sess = scoring.session_for(gbm)
        leaf_f = sess.leaf_matrix(adapted, fr.nrows)
        binned = gbm.spec.bin_columns(adapted)
        leaf_e = np.asarray(gbm.forest.leaf_index(binned))[: fr.nrows]
        assert np.array_equal(leaf_f, leaf_e)
        # host-packed fallback (plane off) is bitwise too
        import os

        os.environ["H2O_TPU_SHARDED_PLANE"] = "0"
        try:
            sess2 = scoring.ScoringSession(gbm)
            leaf_h = sess2.leaf_matrix(gbm.adapt_test(fr), fr.nrows)
        finally:
            del os.environ["H2O_TPU_SHARDED_PLANE"]
        assert np.array_equal(leaf_h, leaf_e)

    @pytest.mark.parametrize("la_type", ["Path", "Node_ID"])
    def test_leaf_assignment_matches_legacy(self, cl, gbm, monkeypatch,
                                            la_type):
        fr = _score_frame(150, 81)
        fused = gbm.predict_leaf_node_assignment(fr, type=la_type)
        monkeypatch.setenv("H2O_TPU_SCORE_FAST", "0")   # legacy eager path
        legacy = gbm.predict_leaf_node_assignment(fr, type=la_type)
        _assert_frames_bitwise(legacy, fused, fr.nrows)

    def test_staged_proba_matches_legacy(self, cl, gbm, monkeypatch):
        fr = _score_frame(140, 82)
        fused = gbm.staged_predict_proba(fr)
        monkeypatch.setenv("H2O_TPU_SCORE_FAST", "0")
        legacy = gbm.staged_predict_proba(fr)
        _assert_frames_bitwise(legacy, fused, fr.nrows)

    def test_leaf_matrix_multiprocess_ineligible_uses_eager_path(
            self, cl, gbm, monkeypatch):
        """On a simulated multi-process cloud, a frame the sharded view
        refuses must NOT take the host-gather fallback (it would pull
        non-addressable columns) — leaf_matrix keeps the eager
        device-side pass, in lockstep like predict_batch's generic
        fallback, and stays bitwise."""
        import jax

        from h2o3_tpu import scoring

        fr = _score_frame(130, 87)
        adapted = gbm.adapt_test(fr)
        ref = np.asarray(gbm.forest.leaf_index(
            gbm.spec.bin_columns(adapted)))[: fr.nrows]
        sess = scoring.ScoringSession(gbm)
        monkeypatch.setenv("H2O_TPU_SHARDED_PLANE", "0")   # view refuses
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        scoring.reset_dispatch_counters()
        leaf = sess.leaf_matrix(adapted, fr.nrows)
        monkeypatch.undo()
        assert np.array_equal(leaf, ref)
        # proof the eager path ran: no fused leaf program was dispatched
        # (and _features' host gather — which would np.asarray a
        # non-addressable column on a real cloud — was never entered)
        assert not scoring.dispatch_counters(), scoring.dispatch_counters()

    def test_leaf_programs_use_explain_family(self, cl, gbm):
        """Fused leaf compiles land in the compile ledger under the
        'explain' family (and count as cached-family compiles)."""
        from h2o3_tpu import scoring
        from h2o3_tpu.obs import compiles

        sess = scoring.ScoringSession(gbm)
        fr = _score_frame(90, 83)
        before = compiles.family_table().get("explain", {}).get(
            "compiles", 0)
        sess.leaf_matrix(gbm.adapt_test(fr), fr.nrows)
        after = compiles.family_table()["explain"]["compiles"]
        assert after == before + 1


# ---------------------------------------------------------------------------
# /4 async route + contributions over real HTTP
# ---------------------------------------------------------------------------

def _post(base, path):
    req = urllib.request.Request(base + path, data=b"", method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=60) as r:
        return json.loads(r.read())


class TestRestExplainabilityAndV4:
    @pytest.fixture(scope="class")
    def srv(self, cl):
        from h2o3_tpu.api.server import start_server

        srv = start_server(port=0)
        yield srv
        srv.stop()

    def test_v4_async_route_rides_fused_path_bitwise(self, cl, gbm, srv):
        from h2o3_tpu.core.dkv import DKV

        fr = _score_frame(210, 84)
        fr._key = type(fr._key)("v4_fused_in.hex")
        fr.install()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            ref = gbm.predict(fr)
            out = _post(base, f"/4/Predictions/models/{gbm.key}/frames/"
                              f"{fr.key}")
            job_key = out["job"]["key"]["name"]
            dest = out["dest"]["name"]
            deadline = time.time() + 120
            while time.time() < deadline:
                st = _get(base, f"/3/Jobs/{job_key}")["jobs"][0]
                if st["status"] not in ("CREATED", "RUNNING"):
                    break
                time.sleep(0.05)
            assert st["status"] == "DONE", st
            pred = DKV.get(dest)
            assert pred is not None
            _assert_frames_bitwise(ref, pred, fr.nrows)
        finally:
            fr.delete()

    def test_v4_saturation_sheds_synchronous_429(self, cl, gbm, srv,
                                                 monkeypatch):
        """A /4 request the admission gate would shed must get the
        synchronous 429 + Retry-After at the handler — a failed async
        job would carry no backoff hint."""
        from h2o3_tpu import admission

        fr = _score_frame(64, 88)
        fr._key = type(fr._key)("v4_shed_in.hex")
        fr.install()
        monkeypatch.setenv("H2O_TPU_SCORE_SLO_MS", "50")
        try:
            base = f"http://127.0.0.1:{srv.port}"
            admission.CONTROLLER.reset()
            # saturate the gate: limit-consuming holders + a slow ring
            for _ in range(32):
                admission.CONTROLLER.note_latency(str(gbm.key), 5000.0)
            g = admission.CONTROLLER._gate(str(gbm.key))
            with g.cond:
                g.inflight = admission.CONTROLLER._limit(g)
            try:
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _post(base, f"/4/Predictions/models/{gbm.key}/frames/"
                                f"{fr.key}")
                assert ei.value.code == 429
                assert ei.value.headers.get("Retry-After") is not None
            finally:
                with g.cond:
                    g.inflight = 0
        finally:
            fr.delete()
            admission.CONTROLLER.reset()

    def test_v3_contributions_match_eager(self, cl, gbm, srv):
        from h2o3_tpu.core.dkv import DKV

        fr = _score_frame(120, 85)
        fr._key = type(fr._key)("contrib_in.hex")
        fr.install()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            ref = gbm.predict_contributions(fr)
            out = _post(base, f"/3/Predictions/models/{gbm.key}/frames/"
                              f"{fr.key}?predict_contributions=true")
            pred = DKV.get(out["predictions_frame"]["name"])
            _assert_frames_bitwise(ref, pred, fr.nrows)
        finally:
            fr.delete()

    def test_v3_leaf_and_staged_rest_bitwise(self, cl, gbm, srv,
                                             monkeypatch):
        from h2o3_tpu.core.dkv import DKV

        fr = _score_frame(110, 86)
        fr._key = type(fr._key)("leaf_in.hex")
        fr.install()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            monkeypatch.setenv("H2O_TPU_SCORE_FAST", "0")
            ref_leaf = gbm.predict_leaf_node_assignment(fr, type="Path")
            ref_staged = gbm.staged_predict_proba(fr)
            monkeypatch.delenv("H2O_TPU_SCORE_FAST")
            out = _post(base, f"/3/Predictions/models/{gbm.key}/frames/"
                              f"{fr.key}?leaf_node_assignment=true")
            _assert_frames_bitwise(
                ref_leaf, DKV.get(out["predictions_frame"]["name"]),
                fr.nrows)
            out = _post(base, f"/3/Predictions/models/{gbm.key}/frames/"
                              f"{fr.key}?predict_staged_proba=true")
            _assert_frames_bitwise(
                ref_staged, DKV.get(out["predictions_frame"]["name"]),
                fr.nrows)
        finally:
            fr.delete()


# ---------------------------------------------------------------------------
# SLO-adaptive admission (unit)
# ---------------------------------------------------------------------------

class TestSloAdmission:
    def test_disabled_by_default(self, monkeypatch):
        from h2o3_tpu.admission import AdmissionController

        monkeypatch.delenv("H2O_TPU_SCORE_SLO_MS", raising=False)
        monkeypatch.delenv("H2O_TPU_SCORE_MAX_INFLIGHT", raising=False)
        ctl = AdmissionController()
        with ctl.slot("m"):
            pass
        assert ctl.admitted == 0          # gate disabled: zero overhead

    def test_aimd_decreases_on_breach(self, monkeypatch):
        from h2o3_tpu.admission import AdmissionController

        monkeypatch.setenv("H2O_TPU_SCORE_SLO_MS", "50")
        ctl = AdmissionController()
        for _ in range(64):
            ctl.note_latency("m", 500.0)
        assert ctl.derived_limits()["m"] == 1

    def test_aimd_grows_only_under_pressure(self, monkeypatch):
        from h2o3_tpu.admission import AdmissionController

        monkeypatch.setenv("H2O_TPU_SCORE_SLO_MS", "100")
        ctl = AdmissionController()
        # fast traffic, NO pressure: limit stays at its seed
        for _ in range(64):
            ctl.note_latency("idle", 2.0)
        seed = ctl.derived_limits()["idle"]
        g = ctl._gate("busy")
        for i in range(64):
            with g.cond:
                g.inflight = ctl._limit(g)     # fake demand pressure
            ctl.note_latency("busy", 2.0)
        with g.cond:
            g.inflight = 0
        assert ctl.derived_limits()["idle"] == seed
        assert ctl.derived_limits()["busy"] > seed

    def test_static_knob_caps_derived_limit(self, monkeypatch):
        from h2o3_tpu.admission import AdmissionController

        monkeypatch.setenv("H2O_TPU_SCORE_SLO_MS", "100")
        monkeypatch.setenv("H2O_TPU_SCORE_MAX_INFLIGHT", "2")
        ctl = AdmissionController()
        g = ctl._gate("m")
        for _ in range(64):
            with g.cond:
                g.inflight = 2
            ctl.note_latency("m", 1.0)
        with g.cond:
            g.inflight = 0
        assert ctl.derived_limits()["m"] <= 2

    def test_queue_time_gate_sheds_429_with_derived_retry_after(
            self, monkeypatch):
        from h2o3_tpu.admission import (AdmissionController,
                                        AdmissionRejected)

        monkeypatch.setenv("H2O_TPU_SCORE_SLO_MS", "100")
        ctl = AdmissionController()
        for _ in range(32):
            ctl.note_latency("m", 4000.0)      # mean 4s >> 100ms SLO
        limit = ctl.derived_limits()["m"]
        started = threading.Event()
        release = threading.Event()

        def hold():
            with ctl.slot("m"):
                started.set()
                release.wait(timeout=30)

        holders = [threading.Thread(target=hold) for _ in range(limit)]
        for t in holders:
            t.start()
        started.wait(timeout=10)
        time.sleep(0.1)
        try:
            with pytest.raises(AdmissionRejected) as ei:
                with ctl.slot("m"):
                    pass
            assert ei.value.status == 429
            # drain-rate-derived: backlog × mean / limit = 1 × 4s / 1 = 4s,
            # NOT the old constant 1s
            assert ei.value.retry_after_s >= 2.0
            assert ctl.shed_slo == 1
        finally:
            release.set()
            for t in holders:
                t.join()

    def test_snapshot_carries_slo_block(self, monkeypatch):
        from h2o3_tpu.admission import AdmissionController

        monkeypatch.setenv("H2O_TPU_SCORE_SLO_MS", "123")
        ctl = AdmissionController()
        ctl.note_latency("m", 10.0)
        snap = ctl.snapshot()
        assert snap["slo_ms"] == 123.0
        assert snap["models"]["m"]["limit"] >= 1
        assert "p99_ms" in snap["models"]["m"]


# ---------------------------------------------------------------------------
# saturation soak (slow; real HTTP)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestSaturationSoak:
    def test_soak_holds_p99_within_slo_while_shedding(self, cl, gbm,
                                                      monkeypatch):
        """Drive sustained concurrency past the adaptive limit: requests
        that are served stay within the latency SLO at p99, the overflow
        sheds as 429 with a Retry-After, and the soak compiles ZERO new
        fused scoring programs (compile-ledger-asserted)."""
        from h2o3_tpu import admission, scoring
        from h2o3_tpu.api.server import start_server
        from h2o3_tpu.obs import compiles

        fr = _score_frame(128, 90)
        fr._key = type(fr._key)("soak_in.hex")
        fr.install()
        srv = start_server(port=0)
        try:
            base = (f"http://127.0.0.1:{srv.port}/3/Predictions/models/"
                    f"{gbm.key}/frames/{fr.key}")

            def one():
                req = urllib.request.Request(base, data=b"",
                                             method="POST")
                t0 = time.perf_counter()
                try:
                    with urllib.request.urlopen(req, timeout=120) as r:
                        json.loads(r.read())
                    return ("ok", time.perf_counter() - t0, None)
                except urllib.error.HTTPError as e:
                    return ("http", time.perf_counter() - t0,
                            (e.code, e.headers.get("Retry-After")))

            # warm every program, then size the SLO from observed latency.
            # Coalesced flushes land in the bucket matching the FLUSH's
            # total rows, so warm the whole ladder (a warm production
            # server holds all bucket executables — from traffic or the
            # persistent compile cache) before asserting zero recompiles.
            sess = scoring.session_for(gbm)
            for warm_n in (100, 500, 2000, 10000):
                sess.predict(_score_frame(warm_n, 200 + warm_n))
            for _ in range(3):
                st, dt, _x = one()
                assert st == "ok"
            base_ms = dt * 1000.0
            slo = max(2500.0, 40 * base_ms)
            monkeypatch.setenv("H2O_TPU_SCORE_SLO_MS", str(slo))
            monkeypatch.setenv("H2O_TPU_SCORE_QUEUE_CAP", "2")
            admission.CONTROLLER.reset()
            ledger0 = compiles.family_table().get("scoring", {}).get(
                "compiles", 0)
            sess_compiles0 = scoring.session_for(gbm).fused_compiles

            results = []
            res_lock = threading.Lock()
            stop = time.time() + 6.0

            def client():
                # a real client honors Retry-After; hammering without
                # backoff would measure GIL starvation of the in-process
                # server, not the admission behavior under load
                while time.time() < stop:
                    r = one()
                    with res_lock:
                        results.append(r)
                    if r[0] == "http" and r[2][1]:
                        time.sleep(min(float(r[2][1]), 0.25))

            ths = [threading.Thread(target=client) for _ in range(16)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()

            ok_lat = sorted(dt for st, dt, _x in results if st == "ok")
            rejects = [x for st, _dt, x in results if st == "http"]
            assert ok_lat, "soak served nothing"
            assert rejects, "soak never shed — not saturated"
            assert all(code in (429, 503) and ra is not None
                       for code, ra in rejects), rejects[:5]
            p99 = ok_lat[min(len(ok_lat) - 1,
                             int(len(ok_lat) * 0.99))] * 1000.0
            assert p99 <= slo, (p99, slo, len(ok_lat), len(rejects))
            # zero fused recompiles during the soak (the warm-bucket
            # contract: saturation must not thrash the compile caches)
            assert compiles.family_table()["scoring"]["compiles"] == \
                ledger0
            assert scoring.session_for(gbm).fused_compiles == \
                sess_compiles0
            # at least one 429 carries the drain-derived Retry-After
            assert any(int(ra) >= 1 for _c, ra in rejects)
        finally:
            srv.stop()
            fr.delete()
            admission.CONTROLLER.reset()

"""AOT scoring artifacts, persistent compile cache, admission control.

The PR-6 subsystem contracts:
- export -> (fresh-process) standalone-runner predictions are BITWISE
  identical to in-process fused serving;
- a second server start against a warm $H2O_TPU_COMPILE_CACHE_DIR compiles
  ZERO fused programs (counter-asserted);
- admission-control overflow returns 429/503 + Retry-After while admitted/
  queued requests still complete;
- corrupt/truncated artifacts (and tampered executable blobs) are rejected
  through the schema-validated manifest / restricted unpickler, never
  half-loaded.
"""

import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from h2o3_tpu.core.frame import Column, Frame


def _bits(a):
    return np.ascontiguousarray(np.asarray(a, np.float32)).view(np.uint32)


def _train_frame(n=500, classes=2, seed=11):
    rng = np.random.default_rng(seed)
    fr = Frame()
    logit = np.zeros(n)
    for i in range(4):
        x = rng.standard_normal(n)
        logit += x * ((-1) ** i) * 0.7
        fr.add(f"n{i}", Column.from_numpy(x))
    codes = rng.integers(0, 3, n)
    fr.add("c0", Column.from_numpy(np.array(["a", "b", "c"])[codes],
                                   ctype="enum"))
    if classes == 2:
        y = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)), "Y", "N")
    else:
        y = np.array(["c%d" % (v % classes) for v in
                      rng.integers(0, classes, n)])
    fr.add("y", Column.from_numpy(y, ctype="enum"))
    return fr


def _test_frame(n=80, seed=13):
    rng = np.random.default_rng(seed)
    fr = Frame()
    for i in range(4):
        fr.add(f"n{i}", Column.from_numpy(rng.standard_normal(n)))
    fr.add("c0", Column.from_numpy(
        np.array(["a", "b", "c"])[rng.integers(0, 3, n)], ctype="enum"))
    return fr


def _frame_to_csv(fr, path, n):
    cols = []
    for nm in fr.names:
        c = fr.col(nm)
        vals = np.asarray(c.data)[:n]
        if c.is_categorical:
            vals = np.asarray(c.domain, object)[vals]
        cols.append((nm, vals))
    with open(path, "w") as f:
        f.write(",".join(nm for nm, _ in cols) + "\n")
        for i in range(n):
            f.write(",".join(str(v[i]) for _, v in cols) + "\n")


@pytest.fixture(scope="module")
def gbm(cl):
    from h2o3_tpu.models.tree.gbm import GBM

    return GBM(ntrees=5, max_depth=3, seed=7).train(
        y="y", training_frame=_train_frame())


@pytest.fixture(scope="module")
def gbm_multi(cl):
    from h2o3_tpu.models.tree.gbm import GBM

    return GBM(ntrees=3, max_depth=3, seed=9).train(
        y="y", training_frame=_train_frame(classes=3, seed=21))


class TestExportImportRoundtrip:
    def test_loader_roundtrip_is_bitwise_identical(self, cl, gbm, tmp_path):
        from h2o3_tpu import artifact, scoring

        art = str(tmp_path / "art")
        man = artifact.export_model(gbm, art, buckets=[128])
        assert man["model_checksum"]
        loaded = artifact.load_model(art, model_id="art_rt_model")
        test = _test_frame()
        p0 = scoring.session_for(gbm).predict(test)
        p1 = scoring.session_for(loaded).predict(test)
        for col in p0.names:
            assert np.array_equal(_bits(p0.col(col).data),
                                  _bits(p1.col(col).data)), col
        loaded.delete()

    def test_describe_summarizes_manifest(self, cl, gbm, tmp_path):
        from h2o3_tpu import artifact

        art = str(tmp_path / "art")
        artifact.export_model(gbm, art, buckets=[128])
        info = artifact.describe(art)
        assert info["algo"] == "gbm"
        assert info["buckets"] == [128]
        assert info["n_features"] == 5

    def test_unsupported_model_refused(self, cl, tmp_path):
        from h2o3_tpu import artifact
        from h2o3_tpu.models.kmeans import KMeans

        km = KMeans(k=2, seed=3, max_iterations=3).train(
            training_frame=_test_frame(60))
        with pytest.raises(artifact.ArtifactError, match="SharedTree"):
            artifact.export_model(km, str(tmp_path / "km"))
        km.delete()


class TestStandaloneRunner:
    def test_fresh_process_predictions_bitwise(self, cl, gbm, tmp_path):
        """Export -> score in a FRESH python process through the genmodel
        runner -> margins AND probabilities bitwise-equal to the server's
        fused session."""
        from h2o3_tpu import artifact, scoring

        art = str(tmp_path / "art")
        artifact.export_model(gbm, art, buckets=[128])
        test = _test_frame()
        n = test.nrows
        csv = str(tmp_path / "in.csv")
        _frame_to_csv(test, csv, n)

        sess = scoring.session_for(gbm)
        X = sess._features(gbm.adapt_test(test), n)
        ref_marg = np.asarray(sess._margin_x(X))
        import jax.numpy as jnp

        ref_probs = np.asarray(
            gbm._margin_to_raw(jnp.asarray(ref_marg))["probs"])

        raw_npz = str(tmp_path / "raw.npz")
        out_csv = str(tmp_path / "out.csv")
        proc = subprocess.run(
            [sys.executable, "-m", "h2o3_genmodel.aot_predict",
             "--artifact", art, "--input", csv, "--output", out_csv,
             "--raw-npz", raw_npz],
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        with np.load(raw_npz) as z:
            assert np.array_equal(_bits(z["margins"]), _bits(ref_marg))
            assert np.array_equal(_bits(z["probs"]), _bits(ref_probs))

    def test_multinomial_runner_in_process_bitwise(self, cl, gbm_multi,
                                                   tmp_path):
        from h2o3_genmodel.aot import load_artifact
        from h2o3_tpu import artifact, scoring

        art = str(tmp_path / "artm")
        artifact.export_model(gbm_multi, art, buckets=[128])
        test = _test_frame(50, seed=31)
        sess = scoring.session_for(gbm_multi)
        X = sess._features(gbm_multi.adapt_test(test), 50)
        ref = np.asarray(sess._margin_x(X))
        s = load_artifact(art)
        got = s.margins(s.pack_features({
            nm: (np.asarray(test.col(nm).data)[:50]
                 if not test.col(nm).is_categorical else
                 np.asarray(test.col(nm).domain,
                            object)[np.asarray(test.col(nm).data)[:50]])
            for nm in test.names}))
        assert np.array_equal(_bits(got), _bits(ref))

    def test_stablehlo_fallback_bitwise(self, cl, gbm, tmp_path):
        """With every serialized executable stripped, the runner compiles
        the shipped StableHLO — the identical program — and stays
        bitwise-equal."""
        from h2o3_genmodel.aot import load_artifact
        from h2o3_tpu import artifact, scoring

        art = str(tmp_path / "arth")
        artifact.export_model(gbm, art, buckets=[128])
        mpath = os.path.join(art, "manifest.json")
        m = json.load(open(mpath))
        m["executables"] = []
        json.dump(m, open(mpath, "w"))
        test = _test_frame(40, seed=41)
        sess = scoring.session_for(gbm)
        X = sess._features(gbm.adapt_test(test), 40)
        ref = np.asarray(sess._margin_x(X))
        s = load_artifact(art)
        got = s.margins(X)
        assert s.loaded_from == {128: "hlo"}
        assert np.array_equal(_bits(got), _bits(ref))


class TestPersistentCompileCache:
    def test_warm_restart_compiles_zero_programs(self, cl, gbm, tmp_path,
                                                 monkeypatch):
        """First session populates $H2O_TPU_COMPILE_CACHE_DIR; a fresh
        session (the 'second server start') must dispatch entirely from
        the cache — fused compile counter stays at zero."""
        from h2o3_tpu import scoring
        from h2o3_tpu.artifact import compile_cache

        monkeypatch.setenv("H2O_TPU_COMPILE_CACHE_DIR",
                           str(tmp_path / "cc"))
        test = _test_frame(30, seed=51)
        compile_cache.reset_stats()
        cold = scoring.ScoringSession(gbm)
        cold.predict(test)
        assert cold.fused_compiles >= 1
        assert compile_cache.fused_compile_count() == cold.fused_compiles
        stored = compile_cache.stats()["stores"]
        assert stored >= 1

        scoring.purge()                   # "server restart": sessions gone
        compile_cache.reset_stats()
        warm = scoring.ScoringSession(gbm)
        p_warm = warm.predict(test)
        assert compile_cache.fused_compile_count() == 0
        assert warm.fused_compiles == 0
        assert warm.cache_hits >= 1
        # and the cached executable scores identically
        p_cold = cold.predict(test)
        for col in p_cold.names:
            assert np.array_equal(_bits(p_cold.col(col).data),
                                  _bits(p_warm.col(col).data))

    def test_cache_disabled_without_env(self, cl, gbm, monkeypatch):
        from h2o3_tpu import scoring
        from h2o3_tpu.artifact import compile_cache

        monkeypatch.delenv("H2O_TPU_COMPILE_CACHE_DIR", raising=False)
        assert not compile_cache.enabled()
        sess = scoring.ScoringSession(gbm)
        sess.predict(_test_frame(10, seed=61))
        assert sess.fused_compiles >= 1    # compiled, nothing persisted
        assert compile_cache.stats()["stores"] == 0


class TestCorruptArtifactRejection:
    def _export(self, gbm, tmp_path):
        from h2o3_tpu import artifact

        art = str(tmp_path / "art")
        artifact.export_model(gbm, art, buckets=[64])
        return art

    def test_truncated_payload_rejected(self, cl, gbm, tmp_path):
        from h2o3_tpu import artifact

        art = self._export(gbm, tmp_path)
        p = os.path.join(art, "forest.npz")
        data = open(p, "rb").read()
        open(p, "wb").write(data[: len(data) // 2])
        with pytest.raises(artifact.ArtifactError, match="checksum"):
            artifact.load_model(art, model_id="nope")

    def test_future_format_version_rejected(self, cl, gbm, tmp_path):
        from h2o3_tpu import artifact

        art = self._export(gbm, tmp_path)
        mpath = os.path.join(art, "manifest.json")
        m = json.load(open(mpath))
        m["format_version"] = 99
        json.dump(m, open(mpath, "w"))
        with pytest.raises(artifact.ArtifactError, match="format_version"):
            artifact.describe(art)

    @pytest.mark.parametrize("forest_args", [
        None,                  # as the parent wrote a manifest: no such key
        ["feat", "thresh_bin", "na_left", "left", "right", "leaf_val",
         "cat_split", "cat_table", "tree_class", "na_bins"]])
    def test_programs_of_another_forest_layout_refused(self, cl, gbm,
                                                       tmp_path, forest_args):
        """An artifact whose programs were lowered for the stored arrays
        (before the level-ordered walk) is refused by the standalone runner
        with a message that says what to do, on the serialized executable
        and the StableHLO alike, never called with the level view bound to
        the old positions. A serving cloud still imports it: it reads the
        stored arrays and compiles its own programs."""
        from h2o3_genmodel.aot import ArtifactError, load_artifact
        from h2o3_tpu import artifact

        art = self._export(gbm, tmp_path)
        mpath = os.path.join(art, "manifest.json")
        m = json.load(open(mpath))
        assert m.pop("forest_args") == ["nodes", "cat_words", "tree_class",
                                        "na_bins", "starts"]
        if forest_args is not None:
            m["forest_args"] = forest_args
        json.dump(m, open(mpath, "w"))
        with pytest.raises(ArtifactError, match="re-export"):
            load_artifact(art)
        assert artifact.load_model(art, install=False).forest.n_trees == \
            gbm.forest.n_trees

    def test_path_traversal_in_manifest_rejected(self, cl, gbm, tmp_path):
        from h2o3_tpu import artifact

        art = self._export(gbm, tmp_path)
        mpath = os.path.join(art, "manifest.json")
        m = json.load(open(mpath))
        m["files"]["forest"]["name"] = "../../etc/passwd"
        json.dump(m, open(mpath, "w"))
        with pytest.raises(artifact.ArtifactError, match="illegal"):
            artifact.load_model(art)

    def test_tampered_exec_blob_refused_by_restricted_unpickler(
            self, cl, gbm, tmp_path):
        """A checksum-consistent but malicious executable blob (pickle
        smuggling os.system) must be refused by the restricted unpickler,
        not executed and not silently skipped."""
        import hashlib
        import pickle

        from h2o3_genmodel.aot import load_artifact

        art = self._export(gbm, tmp_path)
        evil = pickle.dumps({"v": 1, "payload": b"",
                             "in_tree": os.system, "out_tree": None})
        mpath = os.path.join(art, "manifest.json")
        m = json.load(open(mpath))
        assert m["executables"], "export produced no serialized executable"
        entry = m["executables"][0]
        open(os.path.join(art, entry["name"]), "wb").write(evil)
        entry["sha256"] = hashlib.sha256(evil).hexdigest()
        entry["bytes"] = len(evil)
        json.dump(m, open(mpath, "w"))
        s = load_artifact(art)
        with pytest.raises(pickle.UnpicklingError, match="disallowed"):
            s.margins(np.zeros((4, 5), np.float32))

    def test_missing_manifest_rejected(self, cl, tmp_path):
        from h2o3_tpu import artifact

        with pytest.raises(artifact.ArtifactError, match="manifest"):
            artifact.describe(str(tmp_path / "empty"))


class TestAdmissionControl:
    def test_queue_then_reject_then_timeout(self, cl, monkeypatch):
        from h2o3_tpu import admission

        monkeypatch.setenv("H2O_TPU_SCORE_MAX_INFLIGHT", "1")
        monkeypatch.setenv("H2O_TPU_SCORE_QUEUE_CAP", "1")
        monkeypatch.setenv("H2O_TPU_SCORE_QUEUE_TIMEOUT_S", "0.3")
        ctl = admission.AdmissionController()
        release = threading.Event()
        inside = threading.Event()
        results = {}

        def holder():
            with ctl.slot("m"):
                inside.set()
                release.wait(10)

        t_hold = threading.Thread(target=holder)
        t_hold.start()
        assert inside.wait(5)

        def queued():
            try:
                with ctl.slot("m"):
                    results["queued"] = "ran"
            except admission.AdmissionRejected as e:
                results["queued"] = e.status

        t_q = threading.Thread(target=queued)
        t_q.start()
        # wait until the queued request is actually parked
        for _ in range(100):
            if ctl.snapshot()["models"].get("m", {}).get("queue_depth"):
                break
            import time

            time.sleep(0.01)
        # queue is full now: the next request overflows with 429
        with pytest.raises(admission.AdmissionRejected) as ei:
            with ctl.slot("m"):
                pass
        assert ei.value.status == 429
        assert ei.value.retry_after_s >= 0.1
        release.set()                      # holder exits -> queued one runs
        t_hold.join(5)
        t_q.join(5)
        assert results["queued"] == "ran"
        snap = ctl.snapshot()
        assert snap["rejected"] == 1 and snap["admitted"] == 2

    def test_queue_timeout_maps_to_503(self, cl, monkeypatch):
        from h2o3_tpu import admission

        monkeypatch.setenv("H2O_TPU_SCORE_MAX_INFLIGHT", "1")
        monkeypatch.setenv("H2O_TPU_SCORE_QUEUE_CAP", "4")
        monkeypatch.setenv("H2O_TPU_SCORE_QUEUE_TIMEOUT_S", "0.2")
        ctl = admission.AdmissionController()
        release = threading.Event()
        inside = threading.Event()

        def holder():
            with ctl.slot("m"):
                inside.set()
                release.wait(10)

        t = threading.Thread(target=holder)
        t.start()
        assert inside.wait(5)
        with pytest.raises(admission.AdmissionRejected) as ei:
            with ctl.slot("m"):
                pass
        assert ei.value.status == 503
        release.set()
        t.join(5)

    def test_disabled_by_default(self, cl, monkeypatch):
        from h2o3_tpu import admission

        monkeypatch.delenv("H2O_TPU_SCORE_MAX_INFLIGHT", raising=False)
        ctl = admission.AdmissionController()
        with ctl.slot("m"):
            pass
        assert ctl.snapshot()["admitted"] == 0     # passthrough, no gate

    def test_rest_predict_returns_429_with_retry_after(self, cl, gbm,
                                                       monkeypatch):
        """Hold the single slot, then hit POST /3/Predictions over real
        HTTP: 429 + Retry-After while the admitted request still
        completes."""
        from h2o3_tpu import admission
        from h2o3_tpu.api.server import start_server

        monkeypatch.setenv("H2O_TPU_SCORE_MAX_INFLIGHT", "1")
        monkeypatch.setenv("H2O_TPU_SCORE_QUEUE_CAP", "0")
        test = _test_frame(20, seed=71)
        test.install()
        srv = start_server(port=0)
        try:
            url = (f"http://127.0.0.1:{srv.port}/3/Predictions/models/"
                   f"{gbm.key}/frames/{test.key}")
            release = threading.Event()
            inside = threading.Event()

            def holder():
                with admission.CONTROLLER.slot(str(gbm.key)):
                    inside.set()
                    release.wait(10)

            t = threading.Thread(target=holder)
            t.start()
            assert inside.wait(5)
            req = urllib.request.Request(url, data=b"", method="POST")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            assert ei.value.code == 429
            assert int(ei.value.headers["Retry-After"]) >= 1
            release.set()
            t.join(5)
            # slot free again: the same request now succeeds end-to-end
            with urllib.request.urlopen(req, timeout=60) as r:
                body = json.loads(r.read())
            assert body["predictions_frame"]["name"]
        finally:
            srv.stop()
            test.delete()


class TestArtifactRestRoutes:
    def test_export_inspect_import_over_http(self, cl, gbm, tmp_path):
        from h2o3_tpu import scoring
        from h2o3_tpu.api.server import start_server
        from h2o3_tpu.core.dkv import DKV

        srv = start_server(port=0)
        art = str(tmp_path / "rest_art")
        try:
            base = f"http://127.0.0.1:{srv.port}"
            body = urllib.parse.urlencode(
                {"dir": art, "buckets": "[128]"}).encode()
            with urllib.request.urlopen(urllib.request.Request(
                    f"{base}/3/Artifacts/models/{gbm.key}", data=body,
                    method="POST"), timeout=120) as r:
                out = json.loads(r.read())
            assert out["model_checksum"] and out["buckets"] == [128]

            with urllib.request.urlopen(
                    f"{base}/3/Artifacts?dir={urllib.parse.quote(art)}",
                    timeout=30) as r:
                info = json.loads(r.read())
            assert info["algo"] == "gbm"

            body = urllib.parse.urlencode(
                {"dir": art, "model_id": "rest_art_model"}).encode()
            with urllib.request.urlopen(urllib.request.Request(
                    f"{base}/3/Artifacts/import", data=body,
                    method="POST"), timeout=120) as r:
                out = json.loads(r.read())
            assert out["model_id"] == "rest_art_model"
            loaded = DKV.get("rest_art_model")
            assert loaded is not None
            test = _test_frame(25, seed=81)
            p0 = scoring.session_for(gbm).predict(test)
            p1 = scoring.session_for(loaded).predict(test)
            assert np.array_equal(_bits(p0.col("Y").data),
                                  _bits(p1.col("Y").data))
            loaded.delete()
        finally:
            srv.stop()

    def test_import_rejects_bad_dir_with_400(self, cl, tmp_path):
        from h2o3_tpu.api.server import start_server

        srv = start_server(port=0)
        try:
            body = urllib.parse.urlencode(
                {"dir": str(tmp_path / "nothing")}).encode()
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/3/Artifacts/import",
                    data=body, method="POST"), timeout=30)
            assert ei.value.code == 400
        finally:
            srv.stop()


class TestTreeProgressChunks:
    def test_chunk_roundtrip_and_gc(self, cl, tmp_path, monkeypatch):
        from h2o3_tpu.parallel import ckpt

        monkeypatch.setenv("H2O_TPU_OPLOG_CKPT_DIR", str(tmp_path))
        rng = np.random.default_rng(0)
        packs = [rng.standard_normal((3, 4)).astype(np.float32)
                 for _ in range(3)]
        lv = [rng.standard_normal(5).astype(np.float32) for _ in range(3)]
        lw = [rng.standard_normal((5, 2)).astype(np.float32)
              for _ in range(3)]
        p0 = ckpt.append_job_tree_chunk("jobA", 0, packs[:2], lv[:2],
                                        lw[:2])
        p1 = ckpt.append_job_tree_chunk("jobA", 1, packs[2:], lv[2:],
                                        lw[2:])
        rp, rlv, rlw = ckpt.load_job_tree_chunks([p0, p1])
        assert len(rp) == 3
        for a, b in zip(rp, packs):
            assert np.array_equal(a, b)
        for a, b in zip(rlw, lw):
            assert np.array_equal(a, b)
        ckpt.delete_job_progress("jobA")
        assert not os.path.exists(p0) and not os.path.exists(p1)

    def test_gbm_progress_saves_are_append_only(self, cl, tmp_path,
                                                monkeypatch):
        """A training run's progress states reference suffix chunks, not
        inline forests: each save appends exactly one chunk holding only
        the new trees."""
        from h2o3_tpu.core.job import Job
        from h2o3_tpu.models.tree.gbm import GBM
        from h2o3_tpu.parallel import ckpt

        monkeypatch.setenv("H2O_TPU_JOB_CKPT_ITERS", "2")
        monkeypatch.setenv("H2O_TPU_OPLOG_CKPT_DIR", str(tmp_path))
        captured = []
        orig = ckpt.save_job_progress

        def spy(job_key, iteration, spec, state):
            captured.append((iteration, state))
            return orig(job_key, iteration, spec, state)

        monkeypatch.setattr(ckpt, "save_job_progress", spy)
        fr = _train_frame(200, seed=91)
        b = GBM(ntrees=6, max_depth=2, seed=5)
        job = Job(description="gbm train")
        job.resume_spec = {"algo": "gbm", "params": {},
                           "training_frame": str(fr.key), "y": "y"}
        b._progress_job = job
        b.train(y="y", training_frame=fr)
        assert len(captured) >= 2
        for i, (iteration, state) in enumerate(captured):
            assert "packs" not in state, "inline O(forest) state is back"
            assert len(state["tree_chunks"]) == i + 1     # ONE new chunk
            assert state["n_tree_entries"] == iteration
        # chunks from save k are a strict prefix of save k+1's
        assert captured[0][1]["tree_chunks"] == \
            captured[1][1]["tree_chunks"][:1]
        fr.delete()


class TestAdaptiveReplayIdleTimeout:
    def test_env_pin_wins(self, monkeypatch):
        from h2o3_tpu.parallel import watchdog

        monkeypatch.setenv("H2O_TPU_REPLAY_IDLE_S", "777")
        assert watchdog.replay_idle_timeout_s() == 777.0

    def test_default_before_traffic(self, monkeypatch):
        from h2o3_tpu.parallel import oplog, watchdog

        monkeypatch.delenv("H2O_TPU_REPLAY_IDLE_S", raising=False)
        monkeypatch.setattr(oplog, "_OP_TIMES", type(oplog._OP_TIMES)(
            maxlen=32))
        assert watchdog.replay_idle_timeout_s() == \
            watchdog._REPLAY_IDLE_DEFAULT_S

    def test_adapts_to_op_gap_with_clamps(self, monkeypatch):
        from h2o3_tpu.parallel import oplog, watchdog

        monkeypatch.delenv("H2O_TPU_REPLAY_IDLE_S", raising=False)

        def set_gaps(gap_s, n=8):
            q = type(oplog._OP_TIMES)(maxlen=32)
            t = 1000.0
            for _ in range(n):
                q.append(t)
                t += gap_s
            monkeypatch.setattr(oplog, "_OP_TIMES", q)

        set_gaps(30.0)                                   # 20x30 = 600 s
        assert watchdog.replay_idle_timeout_s() == 600.0
        set_gaps(0.01)                                   # clamped low
        assert watchdog.replay_idle_timeout_s() == \
            watchdog._REPLAY_IDLE_MIN_S
        set_gaps(1000.0)                                 # clamped high
        assert watchdog.replay_idle_timeout_s() == \
            watchdog._REPLAY_IDLE_MAX_S


class TestGlmArtifact:
    """ISSUE-13 satellite: the first non-forest class through
    artifact/export + h2o3_genmodel.aot. The exported program IS the
    in-process ``_glm_predict`` jit program (lowered per bucket), so the
    standalone runner is bitwise-identical to ``GLMModel.predict`` —
    including the StableHLO fallback path."""

    def _glm_frames(self, n=600, seed=31):
        rng = np.random.default_rng(seed)
        fr = Frame()
        x1 = rng.standard_normal(n)
        x1[::9] = np.nan
        fr.add("x1", Column.from_numpy(x1))
        fr.add("x2", Column.from_numpy(rng.standard_normal(n)))
        fr.add("g", Column.from_numpy(
            np.array(["a", "b", "c"])[rng.integers(0, 3, n)],
            ctype="enum"))
        y = np.where(rng.random(n) < 1 / (1 + np.exp(
            -np.nan_to_num(x1))), "Y", "N")
        fr.add("y", Column.from_numpy(y, ctype="enum"))
        tn = 150
        tx1 = rng.standard_normal(tn)
        tx1[::5] = np.nan
        test = Frame()
        test.add("x1", Column.from_numpy(tx1))
        test.add("x2", Column.from_numpy(rng.standard_normal(tn)))
        gv = np.array(["a", "b", "c", "zz"])[rng.integers(0, 4, tn)]
        test.add("g", Column.from_numpy(gv, ctype="enum"))
        cols = {"x1": tx1, "x2": np.asarray(test.col("x2").data)[:tn],
                "g": gv}
        return fr, test, cols, tn

    def test_binomial_glm_bitwise_incl_hlo_fallback(self, cl, tmp_path):
        from h2o3_genmodel.aot import load_artifact
        from h2o3_tpu import artifact
        from h2o3_tpu.models.glm import GLM

        fr, test, cols, tn = self._glm_frames()
        m = GLM(family="binomial").train(y="y", training_frame=fr)
        art = str(tmp_path / "glm_art")
        man = artifact.export_model(m, art, buckets=[256])
        assert man["model_type"] == "glm"
        ref = m.predict(test)
        s = load_artifact(art)
        out = s.score(cols)
        for lvl in ("N", "Y"):
            assert np.array_equal(_bits(ref.col(lvl).data[:tn]),
                                  _bits(out[lvl])), lvl
        dom = ref.col("predict").domain
        lab = [dom[i] for i in np.asarray(ref.col("predict").data)[:tn]]
        assert lab == [str(v) for v in out["predict"]]
        # the StableHLO fallback executes the exporter's exact program:
        # margins stay bitwise without a loadable serialized executable
        s2 = load_artifact(art)
        s2.manifest["executables"] = []
        out2 = s2.score(cols)
        assert s2.loaded_from == {256: "hlo"}
        assert np.array_equal(_bits(out["Y"]), _bits(out2["Y"]))
        m.delete()

    def test_regression_and_multinomial_glm_bitwise(self, cl, tmp_path):
        from h2o3_genmodel.aot import load_artifact
        from h2o3_tpu import artifact
        from h2o3_tpu.models.glm import GLM

        rng = np.random.default_rng(33)
        n = 500
        fr = Frame()
        x = rng.standard_normal(n)
        fr.add("x1", Column.from_numpy(x))
        fr.add("x2", Column.from_numpy(rng.standard_normal(n)))
        fr.add("y", Column.from_numpy(2 * x + rng.normal(0, 0.1, n)))
        mr = GLM(family="gaussian").train(y="y", training_frame=fr)
        art = str(tmp_path / "glm_reg")
        artifact.export_model(mr, art, buckets=[128])
        t = {"x1": rng.standard_normal(90), "x2": rng.standard_normal(90)}
        tf = Frame()
        tf.add("x1", Column.from_numpy(t["x1"]))
        tf.add("x2", Column.from_numpy(t["x2"]))
        ref = mr.predict(tf)
        out = load_artifact(art).score(t)
        assert np.array_equal(_bits(ref.col("predict").data[:90]),
                              _bits(out["predict"]))
        mr.delete()

        fr3 = Frame()
        fr3.add("x1", Column.from_numpy(x))
        fr3.add("x2", Column.from_numpy(rng.standard_normal(n)))
        fr3.add("y", Column.from_numpy(
            np.array(["r", "s", "t"])[np.clip((x + 1.2).astype(int), 0,
                                              2)], ctype="enum"))
        mm = GLM(family="multinomial").train(y="y", training_frame=fr3)
        art3 = str(tmp_path / "glm_multi")
        artifact.export_model(mm, art3, buckets=[128])
        ref3 = mm.predict(tf)
        out3 = load_artifact(art3).score(t)
        for lvl in ("r", "s", "t"):
            assert np.array_equal(_bits(ref3.col(lvl).data[:90]),
                                  _bits(out3[lvl])), lvl
        mm.delete()

    def test_glm_artifact_server_import_bitwise(self, cl, tmp_path):
        """GLM artifacts re-import through the /3/Artifacts path: the
        loader rebuilds coefficients, the DataInfo layout, and the
        threshold metrics, and the imported model's predictions are
        bitwise-identical to the exporting model's."""
        from h2o3_tpu import artifact
        from h2o3_tpu.models.glm import GLM

        fr, test, _cols, tn = self._glm_frames(seed=35)
        m = GLM(family="binomial").train(y="y", training_frame=fr)
        art = str(tmp_path / "glm_imp")
        artifact.export_model(m, art, buckets=[128])
        ref = m.predict(test)
        loaded = artifact.load_model(art, model_id="glm_reimported")
        assert loaded.key == "glm_reimported"
        out = loaded.predict(test)
        for lvl in ("N", "Y"):
            assert np.array_equal(
                _bits(np.asarray(ref.col(lvl).data)[:tn]),
                _bits(np.asarray(out.col(lvl).data)[:tn])), lvl
        assert (np.asarray(ref.col("predict").data)[:tn].tolist()
                == np.asarray(out.col("predict").data)[:tn].tolist())
        loaded.delete()
        m.delete()

    def test_unsupported_glm_shapes_refused(self, cl, tmp_path):
        from h2o3_tpu import artifact
        from h2o3_tpu.models.glm import GLM

        fr, _t, _c, _n = self._glm_frames(seed=37)
        m = GLM(family="binomial", interactions=["x1", "x2"]).train(
            y="y", training_frame=fr)
        with pytest.raises(artifact.ArtifactError, match="interaction"):
            artifact.export_model(m, str(tmp_path / "glm_bad"))
        m.delete()

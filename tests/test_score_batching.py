"""Micro-batcher for /3/Predictions (scoring.ScoreBatcher).

Concurrent requests against the same model coalesce into one dispatch and
get their exact per-request slices back; requests against different models
ride independent queues. The REST fast path returns the same payload shape
(and bitwise-identical frames) as the legacy per-request route."""

import threading
import time

import numpy as np
import pytest

from h2o3_tpu.core.frame import Column, Frame


def _train_frame(n=1200, seed=0):
    rng = np.random.default_rng(seed)
    fr = Frame()
    x1 = rng.standard_normal(n)
    x2 = rng.standard_normal(n)
    fr.add("x1", Column.from_numpy(x1))
    fr.add("x2", Column.from_numpy(x2))
    y = np.where(rng.random(n) < 1 / (1 + np.exp(-(1.2 * x1 - x2))),
                 "Y", "N")
    fr.add("y", Column.from_numpy(y, ctype="enum"))
    return fr


def _score_frame(n, seed):
    rng = np.random.default_rng(seed)
    fr = Frame()
    fr.add("x1", Column.from_numpy(rng.standard_normal(n)))
    fr.add("x2", Column.from_numpy(rng.standard_normal(n)))
    return fr


@pytest.fixture(scope="module")
def gbm(cl):
    from h2o3_tpu.models.tree.gbm import GBM

    return GBM(ntrees=6, max_depth=3, seed=1).train(
        y="y", training_frame=_train_frame())


@pytest.fixture(scope="module")
def gbm2(cl):
    from h2o3_tpu.models.tree.gbm import GBM

    return GBM(ntrees=4, max_depth=2, seed=2).train(
        y="y", training_frame=_train_frame(seed=5))


def _assert_frames_bitwise(a, b, n):
    assert a.names == b.names
    for name in a.names:
        av = np.asarray(a.col(name).data)[:n]
        bv = np.asarray(b.col(name).data)[:n]
        assert np.array_equal(av, bv), name


def _concurrent_scores(model, frames, n_threads=None):
    """Submit every frame from its own thread through the micro-batcher;
    returns predictions in frame order (raises the first worker error)."""
    from h2o3_tpu import scoring

    n_threads = n_threads or len(frames)
    results = [None] * len(frames)
    errors = []
    barrier = threading.Barrier(n_threads)

    def worker(i):
        try:
            barrier.wait(timeout=30)
            pred, _mm = scoring.score_request(model, frames[i])
            results[i] = pred
        except BaseException as e:   # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(frames))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    if errors:
        raise errors[0]
    return results


class TestCoalescing:
    def test_concurrent_same_model_exact_slices(self, cl, gbm, monkeypatch):
        """Concurrent requests coalesce into fewer dispatches, and every
        request gets back exactly its own rows."""
        from h2o3_tpu import scoring

        sizes = (50, 120, 77, 333)
        frames = [_score_frame(s, s) for s in sizes]
        expected = [gbm.predict(fr) for fr in frames]
        # wide window so barrier-released threads land in ONE batch
        monkeypatch.setenv("H2O_TPU_SCORE_BATCH_WINDOW_MS", "250")
        scoring.purge(str(gbm.key))         # fresh stats
        sess = scoring.session_for(gbm)
        preds = _concurrent_scores(gbm, frames)
        for fr, exp, got in zip(frames, expected, preds):
            _assert_frames_bitwise(exp, got, fr.nrows)
        stats = sess.stats.snapshot()
        assert stats["requests"] == len(frames)
        assert stats["max_batch_requests"] >= 2, stats   # coalesced
        assert stats["batches"] < stats["requests"], stats

    def test_different_models_do_not_block(self, cl, gbm, gbm2,
                                           monkeypatch):
        """A leader sleeping out model A's window must not delay model B:
        B (window 0) completes while A's batch is still open."""
        from h2o3_tpu import scoring

        # warm both sessions so execution time is dispatch-only
        scoring.score_request(gbm, _score_frame(40, 1))
        scoring.score_request(gbm2, _score_frame(40, 2))

        monkeypatch.setenv("H2O_TPU_SCORE_BATCH_WINDOW_MS", "1500")
        a_done = threading.Event()
        a_res = {}

        def run_a():
            a_res["pred"], _ = scoring.score_request(gbm, _score_frame(64, 3))
            a_done.set()

        ta = threading.Thread(target=run_a)
        ta.start()
        time.sleep(0.2)          # A's leader is inside its window now
        monkeypatch.setenv("H2O_TPU_SCORE_BATCH_WINDOW_MS", "0")
        pred_b, _ = scoring.score_request(gbm2, _score_frame(32, 4))
        assert pred_b.nrows == 32
        assert not a_done.is_set(), \
            "model B's request should finish while model A's batch is open"
        assert a_done.wait(timeout=60)
        ta.join(timeout=30)
        assert a_res["pred"].nrows == 64

    def test_batch_error_propagates_to_each_request(self, cl, gbm,
                                                    monkeypatch):
        """A failing frame inside a batch must fail its request (and not
        strand the batcher's leader slot for later requests)."""
        from h2o3_tpu import scoring

        monkeypatch.setenv("H2O_TPU_SCORE_BATCH_WINDOW_MS", "0")
        bad = Frame()
        bad.add("x1", Column.from_numpy(np.array(["a", "b"] * 8),
                                        ctype="enum"))
        bad.add("x2", Column.from_numpy(np.zeros(16)))
        with pytest.raises(ValueError):
            scoring.score_request(gbm, bad)
        # batcher recovered: next request works
        pred, _ = scoring.score_request(gbm, _score_frame(20, 6))
        assert pred.nrows == 20


class TestRestFastPath:
    def test_predictions_route_fast_vs_legacy(self, cl, gbm, monkeypatch):
        import json
        import urllib.request

        from h2o3_tpu.api.server import start_server
        from h2o3_tpu.core.dkv import DKV

        rng = np.random.default_rng(7)
        fr = Frame(key="score_batch_rest.hex")
        fr.add("x1", Column.from_numpy(rng.standard_normal(210)))
        fr.add("x2", Column.from_numpy(rng.standard_normal(210)))
        fr.install()
        srv = start_server(port=0)
        try:
            base = f"http://127.0.0.1:{srv.port}"

            def post(path):
                req = urllib.request.Request(base + path, data=b"",
                                             method="POST")
                with urllib.request.urlopen(req, timeout=120) as r:
                    return json.loads(r.read())

            fkey = str(fr.key)
            out = post(f"/3/Predictions/models/{gbm.key}/frames/{fkey}"
                       "?predictions_frame=fastpred")
            assert out["predictions_frame"]["name"] == "fastpred"
            monkeypatch.setenv("H2O_TPU_SCORE_FAST", "0")
            post(f"/3/Predictions/models/{gbm.key}/frames/{fkey}"
                 "?predictions_frame=slowpred")
            monkeypatch.delenv("H2O_TPU_SCORE_FAST")
            _assert_frames_bitwise(DKV.get("fastpred"), DKV.get("slowpred"),
                                   fr.nrows)
            # observability: the session shows up in /3/ScoringMetrics
            with urllib.request.urlopen(base + "/3/ScoringMetrics",
                                        timeout=30) as r:
                sm = json.loads(r.read())
            assert any(e["model"] == str(gbm.key) for e in sm["models"])
        finally:
            srv.stop()

    def test_incompatible_columns_rejected_before_broadcast(self, cl, gbm):
        """Satellite: column-compat validation happens pre-broadcast and
        returns 400 (not a 500 from inside adapt_test)."""
        import json
        import urllib.error
        import urllib.request

        from h2o3_tpu.api.server import start_server

        bad = Frame()
        bad.add("x1", Column.from_numpy(np.array(["a", "b"] * 30),
                                        ctype="enum"))
        bad.add("x2", Column.from_numpy(np.zeros(60)))
        bad.install()
        srv = start_server(port=0)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            for route in ("/3/Predictions", "/4/Predictions"):
                req = urllib.request.Request(
                    f"{base}{route}/models/{gbm.key}/frames/{bad.key}",
                    data=b"", method="POST")
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=60)
                assert ei.value.code == 400
                body = json.loads(ei.value.read())
                assert "numeric in training, enum in test" \
                    in json.dumps(body)
        finally:
            srv.stop()


@pytest.mark.slow
class TestBatchingStress:
    def test_many_concurrent_mixed_sizes(self, cl, gbm, monkeypatch):
        """Soak: 24 concurrent mixed-size requests through the batcher —
        every response is the exact per-request slice."""
        rng = np.random.default_rng(11)
        sizes = [int(s) for s in rng.integers(5, 2000, 24)]
        frames = [_score_frame(s, 1000 + i) for i, s in enumerate(sizes)]
        expected = [gbm.predict(fr) for fr in frames]
        monkeypatch.setenv("H2O_TPU_SCORE_BATCH_WINDOW_MS", "20")
        preds = _concurrent_scores(gbm, frames)
        for fr, exp, got in zip(frames, expected, preds):
            _assert_frames_bitwise(exp, got, fr.nrows)


# ---------------------------------------------------------------------------
# one window route for one entry or many (ISSUE 37)
# ---------------------------------------------------------------------------

TOP = 128


@pytest.fixture(scope="module")
def small_buckets(cl, gbm):
    """A session on gbm whose bucket ladder is 64/128 rows, so a flush of
    a few hundred rows is several windows."""
    from h2o3_tpu import scoring

    mp = pytest.MonkeyPatch()
    mp.setenv("H2O_TPU_SCORE_BUCKETS", f"64,{TOP}")
    try:
        sess = scoring.ScoringSession(gbm)
    finally:
        mp.undo()
    assert sess.buckets == (64, TOP)
    return sess


def _entries(sess, gbm, sizes, seed=0):
    """[(adapted frame, ShardedFrame view, n)] for fresh frames of `sizes`."""
    out = []
    for i, n in enumerate(sizes):
        adapted = gbm.adapt_test(_score_frame(n, 700 + 10 * seed + i))
        sf = sess._sharded_view(adapted)
        assert sf is not None
        out.append((adapted, sf, n))
    return out


def _compiles():
    from h2o3_tpu.obs import metrics

    return sum(s["value"] for s in metrics.REGISTRY.get(
        "h2o3_backend_compiles_total").snapshot()["samples"])


def _flush_windows(sess, ents, monkeypatch):
    """Run one flush of `ents` through _margins_sharded_batch under a trace;
    -> (per-entry host margins, [(pos, m)] windows dispatched, plan spans)."""
    from h2o3_tpu.memory import stream
    from h2o3_tpu.obs import tracing

    seen = []
    run = stream.run_windows

    def spy(family, n, dispatch, *a, **kw):
        def d(pos, m):
            seen.append((pos, m))
            return dispatch(pos, m)
        return run(family, n, d, *a, **kw)

    monkeypatch.setattr(stream, "run_windows", spy)
    with tracing.root_span("ingress", path="/3/Predictions/x") as root:
        margins, nd = sess._margins_sharded_batch(
            [(sf, n) for _a, sf, n in ents])
    monkeypatch.setattr(stream, "run_windows", run)
    spans = tracing.get_trace(root.span["trace_id"], include_remote=False)
    assert nd == sum(1 for s in spans if s["name"] == "dispatch")
    return ([np.asarray(m) for m in margins], seen,
            sum(1 for s in spans if s["name"] == "plan"))


def _assert_no_straddle(seen, sizes):
    """Every window lies inside one entry's range of the flush's row space
    (entry i starts at sum(ceil(n_j / TOP) * TOP for j < i))."""
    starts = np.cumsum([0] + [-(-n // TOP) * TOP for n in sizes])
    for pos, m in seen:
        e = int(np.searchsorted(starts, pos, side="right")) - 1
        assert pos + m <= starts[e + 1], (pos, m, list(starts))


def _assert_margins_exact(sess, ents, margins):
    """Each entry's margins equal, byte for byte, the entry scored alone
    and the host-packed path; rows past n of its frame are exactly 0.0."""
    for (adapted, sf, n), mg in zip(ents, margins):
        assert mg.shape[0] == sf.padded_rows
        alone = np.asarray(sess._margins_sharded_batch([(sf, n)])[0][0])
        host = sess._margin_x(sess._features(adapted, n))
        assert mg[:n].tobytes() == alone[:n].tobytes() == host.tobytes()
        assert mg[n:].tobytes() == np.zeros_like(mg[n:]).tobytes()


class TestOneWindowRoute:
    @pytest.mark.parametrize("chunk", [None, 100, 40],
                             ids=["full", "chunked-100", "chunked-40"])
    @pytest.mark.parametrize("sizes", [(300, 200), (300, 200, 129)],
                             ids=["two", "three"])
    def test_flush_is_bitwise_one_plan_and_never_straddles(
            self, gbm, small_buckets, monkeypatch, sizes, chunk):
        """A two- and a three-entry flush whose entries end inside a top
        bucket, planned whole or chunked at a window that is no power of
        two (100 snaps to the 64-row bucket, 40 below the smallest bucket
        to 32 rows): one plan, no window across two entries, every margin
        the entry's own."""
        from h2o3_tpu.memory import budget

        sess = small_buckets
        ents = _entries(sess, gbm, sizes)
        if chunk is not None:
            orig = budget.plan

            def fake(fam, rows, row_bytes=None):
                if fam == "scoring" and rows > chunk:
                    return budget.Plan("chunked", chunk, rows, 4.0, 1 << 20)
                return orig(fam, rows, row_bytes)

            monkeypatch.setattr(budget, "plan", fake)
        margins, seen, plans = _flush_windows(sess, ents, monkeypatch)
        assert plans == 1
        win = {None: TOP, 100: 64, 40: 32}[chunk]
        assert {m for _p, m in seen[:-1]} == {win}
        _assert_no_straddle(seen, sizes)
        _assert_margins_exact(sess, ents, margins)

    @pytest.mark.chaos
    def test_a_halved_window_recovers_the_same_bytes(
            self, gbm, small_buckets, monkeypatch):
        """`mem.exhausted` on the second window: the ladder halves to 64
        rows from that window on, no window straddles, and the margins are
        the untroubled ones."""
        from h2o3_tpu.core import failure
        from h2o3_tpu.memory import stream

        sess = small_buckets
        sizes = (300, 200, 129)
        ents = _entries(sess, gbm, sizes, seed=1)
        hits = {"n": 0}
        faultpoint = failure.faultpoint

        def second(name):
            if name == "mem.exhausted":
                hits["n"] += 1
                if hits["n"] == 2:
                    with failure.inject(name, times=1):
                        faultpoint(name)
            faultpoint(name)

        monkeypatch.setattr(failure, "faultpoint", second)
        c0 = stream.counters()
        margins, seen, plans = _flush_windows(sess, ents, monkeypatch)
        monkeypatch.setattr(failure, "faultpoint", faultpoint)
        c1 = stream.counters()
        assert c1["ladder_halvings"] - c0["ladder_halvings"] == 1
        assert c1["ladder_recoveries"] - c0["ladder_recoveries"] == 1
        assert plans == 1
        assert seen[0] == (0, TOP) and seen[1] == (TOP, 64)
        _assert_no_straddle(seen, sizes)
        _assert_margins_exact(sess, ents, margins)

    def test_a_new_combination_of_row_counts_compiles_nothing(
            self, gbm, small_buckets):
        """Two flushes of two small entries, (3, 5) then (4, 7): once the
        bucket programs are warm, the second combination compiles no XLA
        program at all, eager or not (the lookup cell's blocker)."""
        from h2o3_tpu import scoring

        sess = scoring.session_for(gbm)
        first = [_score_frame(n, 900 + n) for n in (3, 5)]
        then = [_score_frame(n, 900 + n) for n in (4, 7)]
        refs = [gbm.predict(fr) for fr in first + then]
        out = sess.predict_batch([(fr, None, True) for fr in first])
        c0 = _compiles()
        out += sess.predict_batch([(fr, None, True) for fr in then])
        assert _compiles() == c0
        for fr, ref, (pred, _mm) in zip(first + then, refs, out):
            _assert_frames_bitwise(ref, pred, fr.nrows)


class TestPackFeaturesArguments:
    def test_no_eager_device_op_before_the_executable(self, gbm,
                                                      small_buckets,
                                                      monkeypatch):
        """On a warm geometry `pack_features` makes no device value of its
        own (pos and n reach the executable as host int32 values) and its
        output is bitwise the host matrix, for pos = 0 and for a tail
        chunk that runs past n."""
        import jax
        import jax.numpy as jnp

        sess = small_buckets
        ((adapted, sf, n),) = _entries(sess, gbm, (300,), seed=2)
        host = sess._features(adapted, n)
        for pos in (0, 256):
            sf.pack_features(pos, n, 64)          # warm this geometry
        made = []

        def counted(fn):
            def wrapper(*a, **kw):
                made.append(fn)
                return fn(*a, **kw)
            return wrapper

        for mod, name in ((jax, "device_put"), (jnp, "asarray"),
                          (jnp, "array"), (jnp, "int32")):
            monkeypatch.setattr(mod, name, counted(getattr(mod, name)))
        c0 = _compiles()
        got = {pos: np.asarray(sf.pack_features(pos, n, 64))
               for pos in (0, 256)}
        monkeypatch.undo()
        assert made == [] and _compiles() == c0
        assert got[0].tobytes() == host[:64].tobytes()
        tail = np.zeros((64, host.shape[1]), np.float32)
        tail[:n - 256] = host[256:]
        assert got[256].tobytes() == tail.tobytes()

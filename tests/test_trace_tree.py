"""One span tree per request (ISSUE 26): REST ingress -> job thread -> the
builders' stages, and ingress -> flush -> the scoring phases, on one
monotonic clock that is mirrored into the profiler's trace.

Tiny frames on the CPU mesh: what is asserted is the shape of the trees,
that spans add no dispatch, compile or sync, and that the clock never
steps; never a time."""

import json
import re
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from h2o3_tpu.core.frame import Column, Frame
from h2o3_tpu.obs import metrics, tracing

pytestmark = pytest.mark.obs

TRAIN_KEY = "trace_tree_train.hex"


def _frame(n=1200, seed=0, response=True):
    rng = np.random.default_rng(seed)
    fr = Frame()
    x1, x2 = rng.standard_normal(n), rng.standard_normal(n)
    fr.add("x1", Column.from_numpy(x1))
    fr.add("x2", Column.from_numpy(x2))
    fr.add("g", Column.from_numpy(
        np.array(["a", "b", "c"])[rng.integers(0, 3, n)], ctype="enum"))
    if response:
        p = 1 / (1 + np.exp(-(1.2 * x1 - x2)))
        fr.add("y", Column.from_numpy(
            np.where(rng.random(n) < p, "Y", "N"), ctype="enum"))
    return fr


@pytest.fixture(scope="module")
def rest(cl):
    """A server over one installed training frame -> (post, trace)."""
    from h2o3_tpu.api.server import start_server

    fr = _frame()
    fr._key = type(fr._key)(TRAIN_KEY)
    fr.install()
    srv = start_server(port=0)
    base = f"http://127.0.0.1:{srv.port}"

    def get(path):
        with urllib.request.urlopen(base + path, timeout=60) as r:
            return json.loads(r.read())

    def post(path, **body):
        req = urllib.request.Request(
            base + path, data=urllib.parse.urlencode(body).encode(),
            method="POST")
        with urllib.request.urlopen(req, timeout=300) as r:
            return r.headers.get("X-H2O3-Trace-Id"), json.loads(r.read())

    def train(algo, **params):
        tid, out = post(f"/3/ModelBuilders/{algo}", training_frame=TRAIN_KEY,
                        response_column="y", **params)
        key = out["job"]["key"]["name"]
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            job = get(f"/3/Jobs/{key}")["jobs"][0]
            if job["status"] not in ("CREATED", "RUNNING"):
                break
            time.sleep(0.02)
        assert job["status"] == "DONE", job.get("exception")
        # the job span closes a moment after the status flips
        for _ in range(200):
            tr = get(f"/3/Trace/{tid}")
            if any(s["name"] == "job" for s in tr["spans"]):
                return tr
            time.sleep(0.01)
        raise AssertionError(f"no job span in trace {tid}: "
                             f"{[s['name'] for s in tr['spans']]}")

    try:
        yield train, post, get
    finally:
        srv.stop()
        fr.delete()


def _children(tree_node, name):
    return [c for c in tree_node["children"] if c["name"] == name]


ALGO_PARAMS = {"gbm": {"ntrees": 3, "max_depth": 3, "seed": 1},
               "drf": {"ntrees": 3, "max_depth": 3, "seed": 1},
               "glm": {"family": "binomial"}}


@pytest.mark.parametrize("algo", sorted(ALGO_PARAMS))
def test_train_post_reaches_the_job_thread(rest, algo):
    """Job.start carries the POST's trace onto its worker thread: one
    place, every builder."""
    train, _post, _get = rest
    tr = train(algo, model_id=f"trace_tree_{algo}", **ALGO_PARAMS[algo])
    (root,) = tr["tree"]
    assert root["name"] == "ingress" and \
        root["attrs"]["path"] == f"/3/ModelBuilders/{algo}"
    (job,) = _children(root, "job")
    assert job["attrs"]["status"] == "DONE"
    assert job["attrs"]["description"] == f"{algo} Model Build"
    # the POST returned long before the job ended: the child outlives it
    assert job["end_ms"] > root["end_ms"]


def test_gbm_train_trace_holds_the_stages(rest):
    train, _post, _get = rest
    tr = train("gbm", model_id="trace_tree_stages", validation_frame=TRAIN_KEY,
               **ALGO_PARAMS["gbm"])
    (job,) = _children(tr["tree"][0], "job")
    stages = [c for c in job["children"]
              if c["name"] in ("bin", "trees", "assemble", "metrics")]
    assert [c["name"] for c in stages] == \
        ["bin", "trees", "assemble", "metrics", "metrics"]
    assert [c["attrs"].get("frame") for c in stages[3:]] == ["train", "valid"]
    # what a shard hands the three trees' all-reduces, from static shapes
    from h2o3_tpu.core.dkv import DKV
    from h2o3_tpu.core.runtime import cluster
    from h2o3_tpu.models.tree import device_tree

    shards = cluster().row_shards
    nbins = tuple(int(b) for b in DKV.get("trace_tree_stages").spec.nbins)
    assert stages[1]["attrs"] == {"ntrees": 3, "rows": 1200, "max_depth": 3,
                                  "route_levels": 9, "route_gather_levels": 0,
                                  "hist_matmul_levels": 9,
                                  "hist_scatter_levels": 0,
                                  "leaf_lowering": "matmul", "shards": shards,
                                  "psum_bytes": 3 * sum(device_tree.psum_bytes(
                                      3, nbins, shards).values())}
    # each metrics pass walks the forest once: 3 trees x 3 levels, from the
    # static widths (count_walk), none past _at_node's rule
    for c in stages[3:]:
        assert c["attrs"]["walk_levels"] == 9
        assert c["attrs"]["walk_gather_levels"] == 0
        assert c["attrs"]["shards"] == shards and c["attrs"]["psum_bytes"] > 0
    for c in stages:
        assert c["parent_id"] == job["span_id"]
        assert job["start_ms"] <= c["start_ms"] <= c["end_ms"] \
            <= job["end_ms"]
    # consecutive, never overlapping: what is left of job is its self time
    for a, b in zip(stages, stages[1:]):
        assert a["end_ms"] <= b["start_ms"]
    assert sum(c["ms"] for c in stages) <= job["ms"] + 0.01
    # trees hands over to assemble at one instant (tracing.advance)
    assert stages[1]["end_ms"] == stages[2]["start_ms"]


def test_an_active_trace_changes_no_dispatch_compile_or_forest(cl):
    """Spans end where the host already blocks; none adds a device sync, a
    dispatch or a program. The same fit and the same requests, with and
    without an active trace."""
    from h2o3_tpu import scoring
    from h2o3_tpu.models.tree.gbm import GBM

    def fit():
        return GBM(ntrees=3, max_depth=3, seed=7).train(
            y="y", training_frame=_frame(seed=5))

    plain = fit()
    with tracing.root_span("ingress", path="/3/ModelBuilders/gbm") as root:
        traced = fit()
    names = {s["name"] for s in tracing.get_trace(
        root.span["trace_id"], include_remote=False)}
    assert {"bin", "trees", "assemble", "metrics"} <= names
    for a, b in zip(plain.forest.arrays(), traced.forest.arrays()):
        assert np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)

    sess = scoring.session_for(plain)
    fr = _frame(700, seed=9, response=False)
    sess.predict(fr)                                   # warm the bucket
    compiles0 = sess.traversal_compiles

    def one_request():
        before = scoring.dispatch_counters()
        scoring.score_request(plain, fr, with_metrics=True)
        after = scoring.dispatch_counters()
        return {k: after[k] - before.get(k, 0) for k in after}

    untraced = one_request()
    with tracing.root_span("ingress", path="/3/Predictions/x") as root:
        traced_counts = one_request()
    assert traced_counts == untraced and sum(untraced.values()) > 0
    assert sess.traversal_compiles == compiles0
    names = [s["name"] for s in tracing.get_trace(
        root.span["trace_id"], include_remote=False)]
    assert {"queue_wait", "flush", "adapt", "pack", "dispatch", "fetch",
            "metrics"} <= set(names)


def test_walk_levels_ride_the_open_span_and_add_no_dispatch(cl):
    """count_walk gives the span open at each of its call sites (a job's
    `metrics`, a flush) `walk_levels` / `walk_gather_levels`: host
    arithmetic on the forest's static widths. The same requests dispatch
    and compile the same with and without a trace to carry them."""
    from h2o3_tpu import scoring
    from h2o3_tpu.models.tree.gbm import GBM

    model = GBM(ntrees=3, max_depth=3, seed=7).train(
        y="y", training_frame=_frame(seed=5))
    fo = model.forest
    assert fo._walk_counts == dict(walk_levels=9, walk_gather_levels=0)
    fr = _frame(700, seed=9, response=False)
    scoring.session_for(model).predict(fr)             # warm the bucket
    binned = model.spec.bin_columns(_frame(64, seed=3, response=False))

    def compiles():
        return sum(s["value"] for s in metrics.REGISTRY.get(
            "h2o3_backend_compiles_total").snapshot()["samples"])

    def work():
        before, c0 = scoring.dispatch_counters(), compiles()
        scoring.score_request(model, fr, with_metrics=True)
        fo.predict_binned(binned)
        fo.leaf_index(binned)
        after = scoring.dispatch_counters()
        return ({k: after[k] - before.get(k, 0) for k in after},
                compiles() - c0)

    work()                                             # compiles happen here
    untraced = work()
    with tracing.root_span("ingress", path="/3/Predictions/x") as root:
        traced = work()
    assert traced == untraced and untraced[1] == 0
    n_dispatch = sum(untraced[0].values())
    assert n_dispatch > 0
    spans = tracing.get_trace(root.span["trace_id"], include_remote=False)
    levels = sum(s["attrs"].get("walk_levels", 0) for s in spans)
    assert levels == 9 * (n_dispatch + 2)              # + the two by hand
    assert all(s["attrs"].get("walk_gather_levels", 0) == 0 for s in spans)
    # a flush's dispatches happen under its `windows` span (ISSUE 36)
    assert {s["name"] for s in spans if "walk_levels" in s["attrs"]} \
        <= {"ingress", "windows"}


def test_spans_never_step_with_the_wall_clock(monkeypatch):
    real = time.time
    with tracing.root_span("ingress") as root:
        with tracing.span("outer"):
            time.sleep(0.002)
            monkeypatch.setattr(time, "time", lambda: real() - 3600.0)
            with tracing.span("inner"):
                time.sleep(0.002)
            tracing.record_span("waited", tracing.context(),
                                tracing.now_ms() - 1.0)
    monkeypatch.undo()
    spans = {s["name"]: s for s in tracing.get_trace(
        root.span["trace_id"], include_remote=False)}
    assert set(spans) == {"ingress", "outer", "inner", "waited"}
    assert all(s["ms"] >= 0 for s in spans.values())
    for child, parent in (("inner", "outer"), ("waited", "outer"),
                          ("outer", "ingress")):
        assert spans[parent]["start_ms"] <= spans[child]["start_ms"] \
            <= spans[child]["end_ms"] <= spans[parent]["end_ms"]
    # epoch-like for /3/Trace readers, whatever time.time() said meanwhile
    assert abs(spans["ingress"]["start_ms"] - real() * 1000.0) < 60_000


def test_coalesced_follower_gets_the_flush_in_its_own_trace(cl, monkeypatch):
    """Two requests in one flush: the shared phases run under the lead's
    context; the follower's trace still says where its time went."""
    from h2o3_tpu import scoring
    from h2o3_tpu.models.tree.gbm import GBM

    model = GBM(ntrees=2, max_depth=2, seed=3).train(
        y="y", training_frame=_frame(seed=11))
    frames = [_frame(300, seed=20 + i, response=False) for i in range(2)]
    scoring.session_for(model).predict(frames[0])      # compile outside
    monkeypatch.setenv("H2O_TPU_SCORE_BATCH_WINDOW_MS", "400")
    ids, errors = {}, []

    def request(i):
        try:
            with tracing.root_span("ingress", path="/3/Predictions/x") as r:
                ids[i] = r.span["trace_id"]
                scoring.score_request(model, frames[i], with_metrics=True)
        except Exception as e:      # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=request, args=(i,)) for i in (0, 1)]
    threads[0].start()
    time.sleep(0.1)                 # inside the lead's batch window
    threads[1].start()
    for t in threads:
        t.join(120)
        assert not t.is_alive()
    assert not errors, errors
    lead, follower = (tracing.get_trace(ids[i], include_remote=False)
                      for i in (0, 1))
    by_name = {s["name"]: s for s in follower}
    assert set(by_name) == {"ingress", "queue_wait", "flush"}
    assert by_name["flush"]["attrs"] == {"lead": ids[0], "requests": 2}
    lead_flush = next(s for s in lead if s["name"] == "flush")
    assert (by_name["flush"]["start_ms"], by_name["flush"]["end_ms"]) == \
        (lead_flush["start_ms"], lead_flush["end_ms"])
    assert {"adapt", "windows", "fetch"} <= {
        s["name"] for s in lead if s["parent_id"] == lead_flush["span_id"]}
    (windows,) = [s for s in lead if s["name"] == "windows"]
    assert {s["parent_id"] for s in lead
            if s["name"] in ("pack", "dispatch")} == {windows["span_id"]}
    # queue_wait runs into flush without a hole, so ingress keeps only the
    # hand-over on either side
    ingress = by_name["ingress"]
    self_ms = ingress["ms"] - by_name["queue_wait"]["ms"] \
        - by_name["flush"]["ms"]
    assert 0 <= self_ms < 0.25 * ingress["ms"], (self_ms, ingress["ms"])


def _counter(name):
    samples = metrics.REGISTRY.get(name).snapshot()["samples"]
    return sum(s["value"] for s in samples)


def test_a_compile_lands_under_the_span_it_happened_in(cl):
    import jax
    import jax.numpy as jnp

    def fresh(x):                   # a program no other test compiles
        return jnp.tanh(x) * 3.0 + jnp.float32(0.125)

    before = _counter("h2o3_backend_compiles_total")
    spans_before = _counter("h2o3_trace_spans_total")
    jax.jit(fresh)(jnp.ones((7, 13))).block_until_ready()
    assert _counter("h2o3_backend_compiles_total") > before
    assert _counter("h2o3_trace_spans_total") == spans_before
    with tracing.root_span("ingress") as root:
        with tracing.span("dispatch") as sp:
            jax.jit(fresh)(jnp.ones((9, 13))).block_until_ready()
    spans = tracing.get_trace(root.span["trace_id"], include_remote=False)
    found = [s for s in spans if s["name"] == "compile"]
    assert found and all(s["parent_id"] == sp.span["span_id"] and
                         s["attrs"]["seconds"] > 0 for s in found)
    # jax's fun_name: the span says which program it was (the eager ones
    # of `jnp.ones` beside it), so a compile inside a flush names the eager
    # op that caused it; an attribute, never a counter label
    programs = {s["attrs"]["program"] for s in found}
    assert "jit(fresh)" in programs
    assert programs <= {"jit(fresh)", "jit(broadcast_in_dim)"}
    assert _counter("h2o3_backend_compile_seconds_total") > 0


def test_spans_show_in_a_profiler_capture(cl, tmp_path):
    """A capture (the bench's SliceTracer, POST /3/Profiler/start) holds the
    program's live spans as host events h2o3.<name>, on the planes the
    device ops' launches lie on; already-timed spans are not mirrored."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.root_span("ingress"):
            with tracing.span("pack", rows=1):
                time.sleep(0.002)
            tracing.record_span("queue_wait", tracing.context(),
                                tracing.now_ms() - 1.0)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    events = {ev.name: ev.duration_ns
              for plane in ProfileData.from_file(path).planes
              if not plane.name.startswith("/device:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("h2o3.")}
    assert set(events) == {"h2o3.ingress", "h2o3.pack"}
    assert events["h2o3.pack"] >= 2_000_000


def test_device_programs_carry_scopes_and_keep_their_names(cl):
    """named_scope is metadata: the per-level stages can be summed from a
    trace, and the XLA module names the benchmark's needles look for
    (jit_tree_program, jit_run) stay."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.models.tree import compressed, device_tree

    mesh = device_tree._mesh()
    shards = device_tree._mesh_size(mesh)
    n, F, maxB, depth = 64 * shards, 3, 8, 2
    grow = device_tree._grow_fn(depth, F, maxB, (maxB,) * F, (False,) * F,
                                1.0, 1e-5, False, mesh, n // shards, 64,
                                device_tree.frontier_cap(F, maxB))
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32)
    lowered = grow.lower(jax.ShapeDtypeStruct((n, F), jnp.uint8), f32, f32,
                         f32, f32, np.zeros(0, np.float32))
    text = lowered.as_text(debug_info=True)
    assert "module @jit_tree_program" in text
    for scope in ["leaf_sums"] + [f"level{d}/{s}" for d in range(depth)
                                  for s in ("hist", "search", "route")]:
        assert scope in text, scope
    assert f"level{depth}/route" in text and f"level{depth}/hist" not in text
    # a cold compile's metadata (conftest turns the compile cache off):
    # `leaf_sums` names the leaf pass's blocked dot and, on a mesh,
    # `leaf_sums/psum` the all-reduce of its (L, 4) result, so a trace sums
    # the pass and psum_on_chip.py its collective by the names they had
    ops = re.findall(r"= \S+ ([\w\-]+)\(.*op_name=\"([^\"]+)\"",
                     lowered.compile().as_text())
    assert [op for op, name in ops if op in ("dot", "convolution")
            and re.search(r"/leaf_sums/while/body/.*dot_general$", name)]
    assert not [op for op, name in ops if op == "scatter"
                and "/leaf_sums/" in name]
    assert bool([op for op, name in ops if op == "all-reduce"
                 and "/leaf_sums/psum/" in name]) == (shards > 1)

    score = compressed._fused_score_fn(depth, 2)
    T, nodes = 2, 7
    forest = dict(
        nodes=jax.ShapeDtypeStruct((T, 7, nodes), jnp.int32),
        cat_words=jax.ShapeDtypeStruct((0, 1), jnp.uint32),
        tree_class=jax.ShapeDtypeStruct((T,), jnp.int32),
        na_bins=jax.ShapeDtypeStruct((F,), jnp.int32),
        starts=jax.ShapeDtypeStruct((T, 2, depth), jnp.int32))
    text = score.lower(
        jax.ShapeDtypeStruct((16, F), jnp.float32),
        jax.ShapeDtypeStruct((F, maxB), jnp.float32),
        jax.ShapeDtypeStruct((F,), jnp.bool_), jnp.float32(0.0),
        *(forest[k] for k in compressed.WALK_ARGS)).as_text(debug_info=True)
    assert "module @jit_run" in text
    assert "bin" in text and "walk" in text


def test_dl_job_spans_and_counters_add_no_dispatch_or_compile(cl, monkeypatch):
    """A DeepLearning fit: `design`, `epochs` and `metrics` end where the
    host already blocks, so a fit under an active trace dispatches the same
    programs as often, compiles what a plain one does and gives the same
    bits; the step and sample counters count on the host."""
    from h2o3_tpu.models import deeplearning as dl_mod
    from h2o3_tpu.models.deeplearning import DeepLearning

    calls = {"train": 0, "pass": 0}

    def counted(name, fn):
        def call(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return call

    monkeypatch.setattr(dl_mod, "_dl_train_steps",
                        counted("train", dl_mod._dl_train_steps))
    monkeypatch.setattr(dl_mod, "_run_pass",
                        counted("pass", dl_mod._run_pass))
    fr = _frame(seed=11)

    def fit():
        c0, d0 = _counter("h2o3_backend_compiles_total"), dict(calls)
        m = DeepLearning(hidden=[8], epochs=2.5, seed=3,
                         mini_batch_size=32).train(y="y", training_frame=fr)
        return m, _counter("h2o3_backend_compiles_total") - c0, \
            {k: calls[k] - d0[k] for k in calls}

    warm, _, _ = fit()
    steps0 = _counter("h2o3_dl_steps_total")
    samples0 = _counter("h2o3_dl_samples_total")
    dispatches0 = _counter("h2o3_dl_dispatches_total")
    plain, plain_compiles, plain_calls = fit()
    with tracing.root_span("ingress",
                           path="/3/ModelBuilders/deeplearning") as root:
        traced, traced_compiles, traced_calls = fit()
    try:
        steps = round(2.5 * 1200 / 32)
        # three epochs' steps, their three losses and the metrics' scoring
        assert plain_calls == traced_calls == {"train": 3, "pass": 4}
        assert traced_compiles == plain_compiles
        for (a, b), (c, d) in zip(plain.params_tree, traced.params_tree):
            assert np.array_equal(np.asarray(a), np.asarray(c))
            assert np.array_equal(np.asarray(b), np.asarray(d))
        spans = {s["name"]: s for s in tracing.get_trace(
            root.span["trace_id"], include_remote=False)}
        assert {"design", "epochs", "metrics"} <= set(spans)
        assert spans["epochs"]["attrs"] == {
            "batch": 32, "steps": steps, "samples": steps * 32,
            "epochs": 2.5, "dispatches": 3}
        assert spans["design"]["end_ms"] <= spans["epochs"]["start_ms"] \
            <= spans["epochs"]["end_ms"] <= spans["metrics"]["start_ms"]
        assert _counter("h2o3_dl_steps_total") - steps0 == 2 * steps
        assert _counter("h2o3_dl_samples_total") - samples0 == 64 * steps
        assert _counter("h2o3_dl_dispatches_total") - dispatches0 == 6
        assert traced.epochs_trained == 2.5
    finally:
        for m in (warm, plain, traced):
            m.delete()

"""The inside of a scoring flush (ISSUE 36): one span a sequential phase
where the host does the work (`view`, `windows` with the memory planner's
`plan` and each window's `pack` and `dispatch` in it, `join`, `lift` under
`flush`), never one a window; two counters at the same boundary; what the
span store's bounds drop is counted. Since ISSUE 37 every entry is windowed
on its own rows, so the coalesced arm's `parts` span is gone.

Small frames on the CPU mesh with the bucket ladder lowered, so that a
flush of two 300-row requests is several windows. What is asserted is the
shape of the tree, the counts, and that a trace changes no dispatch, compile
or served byte; never a time."""

import threading
import time

import numpy as np
import pytest

from h2o3_tpu.obs import metrics, tracing
from tests.test_trace_tree import _frame

pytestmark = pytest.mark.obs

NEW = ("view", "windows", "join", "lift")
# what a two-entry flush may add to its lead's trace, whatever the number of
# windows: view x 2 (x 2 more where the pipeline splice looks first),
# windows and the plan in it, join, lift x 2. A span a window would pass it
# at once.
MAX_NEW_SPANS = 12


@pytest.fixture(scope="module")
def served(cl):
    """A model whose session chunks at 128 rows, two 300-row frames (three
    windows each, alone or coalesced) and one of 100 rows (one window),
    every program compiled."""
    from h2o3_tpu import scoring
    from h2o3_tpu.models.tree.gbm import GBM

    mp = pytest.MonkeyPatch()
    mp.setenv("H2O_TPU_SCORE_BUCKETS", "64,128")
    try:
        model = GBM(ntrees=2, max_depth=2, seed=3).train(
            y="y", training_frame=_frame(1200, 11))
        sess = scoring.session_for(model)
    finally:
        mp.undo()
    assert sess.buckets[-1] == 128
    frames = [_frame(n, seed, response=False)
              for n, seed in ((300, 20), (300, 21), (100, 22))]
    for fr in frames:
        sess.predict(fr)
    sess.predict_batch([(frames[0], None, True), (frames[1], None, True)])
    return model, sess, frames


def _dispatches():
    from h2o3_tpu import scoring

    return sum(scoring.dispatch_counters().values())


def _counter(name, **labels):
    return sum(s["value"] for s in
               metrics.REGISTRY.get(name).snapshot()["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def _compiles():
    return _counter("h2o3_backend_compiles_total")


def _two_requests(model, frames, traced=True):
    """Both frames in one flush through the batcher; -> (lead trace id,
    follower trace id, {i: probabilities})."""
    from h2o3_tpu import scoring

    ids, out, errors = {}, {}, []

    def request(i):
        try:
            if traced:
                with tracing.root_span("ingress",
                                       path="/3/Predictions/x") as r:
                    ids[i] = r.span["trace_id"]
                    pred, _mm = scoring.score_request(model, frames[i],
                                                      with_metrics=True)
            else:
                pred, _mm = scoring.score_request(model, frames[i],
                                                  with_metrics=True)
            out[i] = np.asarray(pred.col(pred.names[-1]).data)
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
    return ids.get(0), ids.get(1), out


def _below(spans, root):
    kids = {}
    for s in spans:
        kids.setdefault(s["parent_id"], []).append(s)
    out, todo = [], [root["span_id"]]
    while todo:
        for c in kids.get(todo.pop(), ()):
            out.append(c)
            todo.append(c["span_id"])
    return out


def test_two_entry_flush_names_every_phase(served, monkeypatch):
    model, _sess, frames = served
    monkeypatch.setenv("H2O_TPU_SCORE_BATCH_WINDOW_MS", "400")
    before = {arm: (_counter("h2o3_score_flush_windows_total", arm=arm),
                    _counter("h2o3_score_flush_entries_total", arm=arm))
              for arm in ("single", "coalesced")}
    d0 = _dispatches()
    lead_id, follower_id, _out = _two_requests(model, frames)
    n_dispatch = _dispatches() - d0
    lead = tracing.get_trace(lead_id, include_remote=False)
    flush = next(s for s in lead if s["name"] == "flush")
    assert flush["attrs"]["requests"] == 2
    direct = [s for s in lead if s["parent_id"] == flush["span_id"]]
    new = [s for s in direct if s["name"] in NEW]
    # every new span is a child of flush; in order, inside it, not overlapping
    assert [s["name"] for s in _below(lead, flush) if s["name"] in NEW] \
        .count("windows") == 1
    assert len(new) == len([s for s in _below(lead, flush)
                            if s["name"] in NEW])
    names = [s["name"] for s in new]
    assert [n for n in names if n != "view"] == \
        ["windows", "join", "lift", "lift"]
    assert names.count("view") in (2, 4) and \
        names.index("windows") > max(i for i, n in enumerate(names)
                                     if n == "view")
    for a, b in zip(new, new[1:]):
        assert a["end_ms"] <= b["start_ms"], (a["name"], b["name"])
    for s in new:
        assert flush["start_ms"] <= s["start_ms"] <= s["end_ms"] \
            <= flush["end_ms"]
    assert len(new) + 1 <= MAX_NEW_SPANS        # + the plan under windows
    assert len([s for s in lead if s["name"] in NEW + ("plan",)]) \
        == len(new) + 1
    # what stays where it was: adapt, fetch, metrics; no coalescing pack
    assert {"adapt", "fetch", "metrics"} <= {s["name"] for s in direct}
    assert "pack" not in {s["name"] for s in direct}
    assert "parts" not in {s["name"] for s in lead}
    # windows: one span; under it one plan, then a pack and a dispatch a
    # window, each entry's 300 rows at 128 a window: 3 + 3 = what the
    # dispatch counters saw
    (win,) = [s for s in new if s["name"] == "windows"]
    under = [s for s in lead if s["parent_id"] == win["span_id"]]
    assert [s["name"] for s in under] == ["plan"] + ["pack", "dispatch"] * 6
    assert under[0]["attrs"] == {"family": "scoring", "mode": "full"}
    assert [s["attrs"]["rows"] for s in under if s["name"] == "dispatch"] \
        == [128, 128, 44] * 2
    assert win["attrs"]["windows"] == n_dispatch == 6
    assert win["attrs"]["arm"] == "coalesced" and \
        win["attrs"]["entries"] == 2
    assert win["attrs"]["rebucket_ms"] == 0.0
    (join,) = [s for s in new if s["name"] == "join"]
    assert join["attrs"]["pieces"] == 6
    assert [s["attrs"]["rows"] for s in new if s["name"] == "lift"] == \
        [300, 300]
    # the follower's trace is what it was
    follower = tracing.get_trace(follower_id, include_remote=False)
    assert {s["name"] for s in follower} == {"ingress", "queue_wait",
                                             "flush"}
    assert next(s for s in follower if s["name"] == "flush")["attrs"] == \
        {"lead": lead_id, "requests": 2}
    # the two counters moved by this flush alone, on its arm
    after = {arm: (_counter("h2o3_score_flush_windows_total", arm=arm),
                   _counter("h2o3_score_flush_entries_total", arm=arm))
             for arm in ("single", "coalesced")}
    assert after["coalesced"] == (before["coalesced"][0] + 6,
                                  before["coalesced"][1] + 2)
    assert after["single"] == before["single"]


@pytest.mark.parametrize("which,windows", [(2, 1), (0, 3)])
def test_single_entry_flush_has_windows_and_no_parts(served, which, windows):
    from h2o3_tpu import scoring

    model, _sess, frames = served
    w0 = _counter("h2o3_score_flush_windows_total", arm="single")
    e0 = _counter("h2o3_score_flush_entries_total", arm="single")
    d0 = _dispatches()
    with tracing.root_span("ingress", path="/3/Predictions/x") as root:
        scoring.score_request(model, frames[which], with_metrics=True)
    spans = tracing.get_trace(root.span["trace_id"], include_remote=False)
    flush = next(s for s in spans if s["name"] == "flush")
    names = [s["name"] for s in spans if s["parent_id"] == flush["span_id"]
             and s["name"] in NEW]
    # join lays the windows' outputs out as the frame's rows, one or many
    assert [n for n in names if n != "view"] == ["windows", "join", "lift"]
    win = next(s for s in spans if s["name"] == "windows")
    # count_walk's sums ride the span open at the dispatch: this one now
    assert win["attrs"] == {"arm": "single", "entries": 1,
                            "windows": windows, "rebucket_ms": 0.0,
                            "walk_levels": 4 * windows,
                            "walk_gather_levels": 0}
    under = [s["name"] for s in spans if s["parent_id"] == win["span_id"]]
    assert sorted(under) == ["dispatch"] * windows + ["pack"] * windows \
        + ["plan"]
    assert _dispatches() - d0 == windows
    assert _counter("h2o3_score_flush_windows_total", arm="single") \
        == w0 + windows
    assert _counter("h2o3_score_flush_entries_total", arm="single") == e0 + 1


def test_a_trace_changes_nothing_a_two_entry_flush_does(served, monkeypatch):
    """The same two-request flush with and without an active trace: the same
    dispatches by path, no compile, the same served bytes, and the spans
    cost no store write when nobody traces."""
    from h2o3_tpu import scoring

    model, sess, frames = served
    monkeypatch.setenv("H2O_TPU_SCORE_BATCH_WINDOW_MS", "400")
    compiles0 = sess.traversal_compiles

    def flush(traced):
        before, c0 = scoring.dispatch_counters(), _compiles()
        s0 = _counter("h2o3_trace_spans_total")
        lead_id, _f, out = _two_requests(model, frames, traced=traced)
        after = scoring.dispatch_counters()
        return ({k: after[k] - before.get(k, 0) for k in after},
                _compiles() - c0, out, lead_id,
                _counter("h2o3_trace_spans_total") - s0)

    flush(True)                         # whatever compiles, compiles here
    counts_u, compiled_u, out_u, _none, stored_u = flush(False)
    counts_t, compiled_t, out_t, lead_id, stored_t = flush(True)
    assert counts_t == counts_u and sum(counts_u.values()) == 6
    assert compiled_t == compiled_u == 0
    assert sess.traversal_compiles == compiles0
    for i in (0, 1):
        assert out_t[i].tobytes() == out_u[i].tobytes()
    assert stored_u == 0 and stored_t > 0
    assert set(NEW) | {"plan"} <= {
        s["name"] for s in tracing.get_trace(lead_id, include_remote=False)}


def test_the_store_counts_what_its_bounds_drop(monkeypatch):
    def dropped(what):
        return _counter("h2o3_trace_dropped_total", what=what)

    monkeypatch.setattr(tracing, "_SPAN_CAP", 4)
    s0, t0 = dropped("span"), dropped("trace")
    with tracing.root_span("ingress") as root:
        for _ in range(6):
            with tracing.span("stage"):
                pass
    spans = tracing.get_trace(root.span["trace_id"], include_remote=False)
    # four stages fill the trace; two more and the root, which finishes
    # last, are turned away: a full trace loses its root first
    assert [s["name"] for s in spans] == ["stage"] * 4
    assert dropped("span") == s0 + 3
    monkeypatch.setattr(tracing, "_SPAN_CAP", 512)
    monkeypatch.setenv("H2O_TPU_OBS_TRACE_CAP", "3")
    before = dropped("trace")
    assert before >= t0
    kept = []
    for _ in range(5):
        with tracing.root_span("ingress") as r:
            kept.append(r.span["trace_id"])
    # the ring held whatever it held; it now holds the newest three
    assert [bool(tracing.get_trace(t, include_remote=False))
            for t in kept] == [False, False, True, True, True]
    assert dropped("trace") - before >= 2
    assert dropped("span") == s0 + 3

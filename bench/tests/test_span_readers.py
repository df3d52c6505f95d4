"""The readers over the program's span trees (bench/harness/spans.py) on
hand-written span lists, the twelve entries in a dry run of both cells, and
the pure parts of gaps_on_chip.py."""

import json
import types

import pytest

from bench.harness import spans
from bench.tests import gaps_on_chip
from bench.tests.test_dry_run import _bench

TRAIN_READ = {n for ns in spans.TRAIN_STAGES.values() for n in ns}
SCORE_READ = {n for ns in spans.SCORE_STAGES.values() for n in ns}


def sp(name, start, end, sid, parent=None, **attrs):
    return {"name": name, "span_id": sid, "parent_id": parent,
            "start_ms": float(start), "end_ms": float(end),
            "ms": float(end - start), "attrs": attrs}


def train_trace(path="/3/ModelBuilders/gbm", t0=0.0):
    """ingress 0-10; job 5-1005 outlives it; the stages leave 9 ms of job;
    a compile under job and a pack under bin are nobody's entry."""
    return [sp("ingress", t0, t0 + 10, "i", path=path),
            sp("job", t0 + 5, t0 + 1005, "j", "i"),
            sp("bin", t0 + 6, t0 + 106, "b", "j"),
            sp("pack", t0 + 50, t0 + 60, "p", "b"),
            sp("compile", t0 + 107, t0 + 109, "c", "j"),
            sp("trees", t0 + 110, t0 + 610, "t", "j"),
            sp("assemble", t0 + 610, t0 + 640, "a", "j"),
            sp("metrics", t0 + 641, t0 + 941, "m1", "j", frame="train"),
            sp("metrics", t0 + 942, t0 + 1002, "m2", "j", frame="valid")]


def test_self_time_overlapping_children_and_a_child_that_outlives():
    parent = sp("flush", 0, 100, "f")
    kids = [sp("pack", 10, 40, "a", "f"), sp("dispatch", 30, 60, "b", "f"),
            sp("fetch", 90, 130, "c", "f"), sp("pack", 500, 600, "d", "x")]
    # union of [10,60] and [90,100] (clipped) = 60 of 100
    assert spans.self_ms(parent, [parent] + kids) == 40
    tr = train_trace()
    assert spans.self_ms(tr[0], tr) == 5          # job covers 5-10 only
    assert spans.self_ms(tr[1], tr) == 1000 - (100 + 2 + 500 + 30 + 300 + 60)


def test_train_stages_add_up_to_the_job():
    tr = train_trace()
    got = {k: spans.stage_ms(tr, names, TRAIN_READ)
           for k, names in spans.TRAIN_STAGES.items()}
    # the compile under job counts to job: no entry reads it
    assert got == {"bin": 100, "trees": 500, "assemble": 30, "metrics": 360,
                   "other": 10}
    assert sum(got.values()) == 1000
    assert spans.stage_ms([tr[0]], ("job",), TRAIN_READ) is None


def score_pair():
    """A lead and the request coalesced behind it."""
    lead = [sp("ingress", 0, 1000, "i", path="/3/Predictions/models/m/f"),
            sp("admission_wait", 1, 2, "aw", "i"),
            sp("queue_wait", 2, 12, "q", "i"),
            sp("oplog.publish", 12, 20, "o", "i"),
            sp("flush", 20, 990, "f", "i"),
            sp("adapt", 21, 31, "ad", "f"),
            sp("pack", 31, 131, "pk", "f"),
            sp("pack", 40, 120, "pk2", "pk"),      # nested same name
            sp("dispatch", 131, 181, "d", "f"),
            sp("fetch", 200, 600, "fe", "f"),
            sp("compile", 300, 350, "c", "fe"),
            sp("metrics", 600, 980, "m", "f")]
    follower = [sp("ingress", 5, 1001, "i2", path="/3/Predictions/models/m/g"),
                sp("queue_wait", 6, 20, "q2", "i2"),
                sp("flush", 20, 990, "f2", "i2", lead="T1")]
    return lead, follower


def test_score_stages_add_up_to_ingress_for_lead_and_follower():
    lead, follower = score_pair()

    def stages(tr):
        return {k: spans.stage_ms(tr, names, SCORE_READ)
                for k, names in spans.SCORE_STAGES.items()}

    got = stages(lead)
    assert got == {"ingress": 1000 - (1 + 10 + 970), "queue": 11,
                   "flush": 970 - (10 + 100 + 50 + 400 + 380),
                   "pack": 10 + 100, "dispatch": 50, "fetch": 400,
                   "metrics": 380}
    assert sum(got.values()) == 1000
    got = stages(follower)
    assert got["flush"] == 970 and got["queue"] == 14 and \
        got["ingress"] == 996 - 984
    assert [got[k] for k in ("pack", "dispatch", "fetch", "metrics")] == \
        [None] * 4


def run_with(traces, **window):
    system = types.SimpleNamespace(spans=lambda root: traces)
    return types.SimpleNamespace(system=system, window=window)


def test_window_leaves_warm_up_and_other_routes_out():
    lead, follower = score_pair()
    other = [sp("ingress", 0, 1, "g", path="/3/Models/bench_model")]
    warm = [sp("ingress", 0, 5000, "w", path="/3/Predictions/models/m/f"),
            sp("fetch", 0, 5000, "wf", "w")]
    traces = [other, follower, lead, warm, warm]        # newest first
    assert spans.window_traces(traces, 2) == [follower, lead]
    assert spans.window_traces(traces, 0) == []
    run = run_with(traces, attempted=2)
    # totals over the window's requests / requests, whoever led the flush
    assert spans.score_stage_ms(run, "score_stage_ms.flush") == \
        (30 + 970) / 2
    assert spans.score_stage_ms(run, "score_stage_ms.fetch") == 400 / 2
    assert spans.score_stage_ms(run_with([other], attempted=1),
                                "score_stage_ms.fetch") is None

    jobs = [train_trace(t0=0.0), train_trace(t0=5000.0),
            train_trace(t0=9000.0)]
    jobs[0][5]["end_ms"] += 20          # newest: trees 520, assemble 10
    jobs[0][6]["start_ms"] += 20
    run = run_with([other] + jobs, jobs=[{}, {}])       # warm-up job is third
    assert spans.train_stage_s(run, "train_stage_s.trees") == 0.510
    assert spans.train_stage_s(run, "train_stage_s.assemble") == 0.020
    assert spans.train_stage_s(run, "train_stage_s.other") == 0.010
    # a program without the spans (the parent commit): nothing to read
    bare = [[sp("ingress", 0, 10, "i", path="/3/ModelBuilders/gbm")]]
    assert spans.train_stage_s(run_with(bare, jobs=[{}]),
                               "train_stage_s.trees") is None


def test_gaps_own_time_and_scope():
    us = 1000                           # gaps under 50 us are not listed
    ops = [["while.1", 0, 100 * us, {}], ["fusion.2", 10 * us, 30 * us, {}],
           ["fusion.3", 50 * us, 40 * us, {}], ["copy.4", 60 * us, 10 * us, {}],
           ["fusion.5", 200 * us, 20 * us, {}]]
    assert gaps_on_chip.own_ns(ops) == [30 * us, 30 * us, 30 * us, 10 * us,
                                        20 * us]
    scope = gaps_on_chip.scope_of
    assert scope({"tf_op": "jit(tree_program)/jit(main)/level3/hist/dot"}) \
        == "level3/hist"
    assert scope({"tf_op": "jit(run)/jit(main)/walk/while/body/gather:",
                  "hlo_category": "x"}) == "walk"
    assert scope({"tf_op": "jit(run)/jit(main)/bin/lt"}) == "bin"
    assert scope({"tf_op": "jit(run)/binary/add"}) is None
    assert scope({"hlo_category": "loop fusion"}) is None
    dev = {"ops": [[n, s, d, {"tf_op": "a/leaf_sums/b"}]
                   for n, s, d, _st in ops],
           "modules": [["jit_tree_program(7)", 0, 150 * us]]}
    host = [("h2o3.ingress", 0, 400 * us), ("h2o3.fetch", 90 * us, 210 * us)]
    t = gaps_on_chip.tables([dev], host)
    assert t["idle_by_span_s"] == {"h2o3.fetch": 100e-6}
    assert t["device_by_scope_s"] == [
        ["jit_tree_program", "leaf_sums", 100e-6],
        ["(no module)", "leaf_sums", 20e-6]]
    assert t["busy_s"] == 120e-6


@pytest.mark.parametrize("cell,stem,n", [("gbm_train", "train_stage_s.", 5),
                                         ("gbm_batch_score",
                                          "score_stage_ms.", 7)])
def test_dry_run_reports_every_stage(cell, stem, n):
    p = _bench("--workload", cell, "--seed", "3000000033", "--seconds", "4",
               "--trace", "1", "--cpu-dry-run")
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    with open(gaps_on_chip.ROOT + "/BENCHMARK.json") as f:
        want = [m["name"] for m in json.load(f)["per_layer"]
                if m["name"].startswith(stem)]
    assert len(want) == n
    got = {k: v["value"] for k, v in out["metrics"].items() if k in want}
    assert sorted(got) == sorted(want) and all(v >= 0 for v in got.values())
    if cell == "gbm_train":             # the stages of one job are its job
        jobs = out["info"]["jobs"]
        lo, hi = min(j["seconds"] for j in jobs), \
            max(j["seconds"] for j in jobs)
        assert 0.5 * lo <= sum(got.values()) <= hi + 0.1

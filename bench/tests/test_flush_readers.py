"""The readers of the spans inside a flush (bench/layer_metrics/
score_flush_ms.py, score_flush_count.py) on a hand-written lead + follower
pair, on a tree of the commit before the spans, on a window the store has
lost part of, and the eleven entries in a dry run of the cell."""

import json

import pytest

from bench.harness import spans
from bench.layer_metrics import score_flush_count, score_flush_ms
from bench.tests import gaps_on_chip
from bench.tests.test_dry_run import _bench
from bench.tests.test_span_readers import run_with, score_pair, sp

PARTS = ("wait", "view", "parts", "plan", "windows", "join", "lift", "self")


def flush_pair():
    """test_span_readers' lead and follower (flush 20-990, one flush of two
    requests), the lead's flush opened up as the program does since ISSUE 36:
    adapt 21-31 and the coalescing pack 131-141 stay children of flush; the
    chunk packs go under `parts`, the dispatches under `windows`; a compile
    under `lift` is nobody's entry and counts to `lift`."""
    lead, follower = score_pair()
    keep = [s for s in lead if s["name"] in ("ingress", "admission_wait",
                                             "queue_wait", "oplog.publish",
                                             "adapt", "fetch", "metrics")]
    fetch = next(s for s in keep if s["name"] == "fetch")
    fetch["start_ms"], fetch["ms"] = 300.0, 300.0
    lead = keep + [
        sp("flush", 20, 990, "f", "i", requests=2),
        sp("view", 31, 33, "v1", "f"), sp("view", 33, 36, "v2", "f"),
        sp("parts", 36, 131, "p", "f"),
        sp("pack", 40, 80, "pk1", "p"), sp("pack", 85, 125, "pk2", "p"),
        sp("pack", 131, 141, "pkc", "f", path="coalesce"),
        sp("windows", 141, 241, "w", "f", windows=3, entries=2,
           arm="coalesced", rebucket_ms=42.5),
        sp("plan", 141, 148, "pl", "w", family="scoring", mode="full"),
        sp("dispatch", 150, 160, "d1", "w"),
        sp("dispatch", 180, 190, "d2", "w"),
        sp("dispatch", 210, 220, "d3", "w"),
        sp("join", 241, 250, "j", "f"),
        sp("lift", 250, 270, "l1", "f"), sp("lift", 270, 300, "l2", "f"),
        sp("compile", 275, 290, "c", "l2", program="jit(pad)")]
    follower[-1]["attrs"]["requests"] = 2
    return lead, follower


def test_the_parts_add_up_to_the_flush_stage():
    lead, follower = flush_pair()
    run = run_with([follower, lead], attempted=2)
    got = {p: score_flush_ms.read(run, "score_flush_ms." + p) for p in PARTS}
    assert got == {"wait": 970 / 2, "view": 5 / 2, "parts": (95 - 80) / 2,
                   "plan": 7 / 2, "windows": (100 - 30 - 7) / 2,
                   "join": 9 / 2, "lift": 50 / 2,
                   # 970 less adapt 10, view 5, parts 95, pack 10, windows
                   # 100, join 9, lift 50, fetch 300, metrics 380
                   "self": 11 / 2}
    flush = spans.score_stage_ms(run, "score_stage_ms.flush")
    assert flush == (970 + 15 + 7 + 63 + 5 + 9 + 50 + 11) / 2
    assert sum(got.values()) == pytest.approx(flush, rel=1e-12)
    # a part of .windows, left out of the sum
    assert score_flush_ms.read(run, "score_flush_ms.rebucket") == 42.5 / 2
    assert score_flush_count.read(run, "score_flush_count.requests") == 2.0
    assert score_flush_count.read(run, "score_flush_count.windows") == 3.0
    # the seven stages read what they read without the new spans' names
    old_lead, old_follower = score_pair()
    assert spans.score_stage_ms(run, "score_stage_ms.dispatch") == 30 / 2
    assert spans.score_stage_ms(run, "score_stage_ms.pack") == \
        (10 + 80 + 10) / 2
    assert spans.score_stage_ms(
        run_with([old_follower, old_lead], attempted=2),
        "score_stage_ms.flush") == (970 + 30) / 2


def test_a_lone_request_has_no_wait_and_a_true_zero():
    lead, _follower = flush_pair()
    next(s for s in lead if s["name"] == "flush")["attrs"]["requests"] = 1
    run = run_with([lead], attempted=1)
    assert score_flush_ms.read(run, "score_flush_ms.wait") == 0.0
    assert score_flush_ms.read(run, "score_flush_ms.self") == 11.0
    assert score_flush_count.read(run, "score_flush_count.requests") == 1.0


def test_a_tree_without_the_spans_gives_none_never_zero():
    lead, follower = score_pair()           # the commit before the spans
    run = run_with([follower, lead], attempted=2)
    for p in PARTS + ("rebucket",):
        assert score_flush_ms.read(run, "score_flush_ms." + p) is None, p
    assert score_flush_count.read(run, "score_flush_count.windows") is None
    # `requests` has been on the flush span since the spans exist at all
    next(s for s in lead if s["name"] == "flush")["attrs"]["requests"] = 2
    assert score_flush_count.read(run, "score_flush_count.requests") == 2.0
    other = [[sp("ingress", 0, 1, "g", path="/3/Models/bench_model")]]
    assert score_flush_count.read(run_with(other, attempted=1),
                                  "score_flush_count.requests") is None


def test_a_window_the_store_has_lost_part_of_gives_none():
    lead, follower = flush_pair()
    traces = [follower, lead] * 10          # 20 of the window's requests
    for attempted, ok in ((20, True), (21, True), (22, False), (40, False)):
        run = run_with(traces, attempted=attempted)
        for read, name in ((score_flush_ms.read, "score_flush_ms.windows"),
                           (score_flush_count.read,
                            "score_flush_count.requests")):
            assert (read(run, name) is not None) == ok, (attempted, name)


def test_dry_run_reports_every_new_entry():
    p = _bench("--workload", "gbm_batch_score", "--seed", "3600000033",
               "--seconds", "4", "--trace", "1", "--cpu-dry-run")
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    with open(gaps_on_chip.ROOT + "/BENCHMARK.json") as f:
        want = [m["name"] for m in json.load(f)["per_layer"]
                if m["name"].startswith(("score_flush_ms.",
                                         "score_flush_count."))]
    assert len(want) == 11
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(want) <= set(got) and all(got[k] >= 0 for k in want)
    parts = sum(got["score_flush_ms." + p] for p in PARTS)
    assert parts == pytest.approx(got["score_stage_ms.flush"], rel=0.01)
    assert got["score_flush_ms.rebucket"] <= got["score_flush_ms.windows"]
    assert 1.0 <= got["score_flush_count.requests"] <= 2.0
    assert got["score_flush_count.windows"] >= 2.0   # 20,000 rows at 16,384

"""The trace -> metrics reduction, on a hand-made trace whose numbers can be
worked out on paper and on one small recorded trace of a gbm_train run on a
TPU v5 lite (first 150 device operations, first 300 program runs, the host's
job_poll span; bench/tests/recorded_trace.json)."""

import json
import os

import pytest

from bench.harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))


def _made():
    ms = 1_000_000
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 0, 10 * ms], ["fusion.2", 5 * ms, 10 * ms],
                ["fusion.1", 40 * ms, 20 * ms], ["copy.3", 80 * ms, 20 * ms]]},
            {"name": "XLA Modules", "events": [
                ["jit_tree_program(1)", 0, 15 * ms],
                ["jit_tree_program(1)", 40 * ms, 20 * ms],
                ["jit_post(2)", 80 * ms, 20 * ms]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                ["job_post", 10 * ms, 20 * ms], ["job_poll", 30 * ms, 70 * ms],
                ["something_else", 0, 100 * ms]]}]},
    ]}


def test_busy_is_the_union_and_gaps_are_named_by_the_host_span():
    r = T.reduce(_made())
    assert r["n_devices"] == 1
    assert r["busy_s"] == pytest.approx(0.055)          # 15 + 20 + 20 ms
    assert r["window_s"] == pytest.approx(0.100)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.030)
    # the 15..40 ms gap lies mostly under job_post, the 60..80 under job_poll
    gaps = dict(r["idle_gaps"])
    assert gaps == {"job_post": pytest.approx(0.025),
                    "job_poll": pytest.approx(0.020)}
    assert T.module_seconds(r, "tree_program") == (2, pytest.approx(0.035))
    assert T.module_seconds(r, "no_such_program") is None


def test_a_longer_stated_window_lowers_the_busy_share_not_the_busy_time():
    r = T.reduce(_made(), window_s=0.2)
    assert r["window_s"] == pytest.approx(0.2)
    assert r["busy_s"] == pytest.approx(0.055)


def test_no_device_plane_reads_nothing():
    t = _made()
    t["planes"] = t["planes"][1:]
    r = T.reduce(t, window_s=1.0)
    assert r["n_devices"] == 0 and r["busy_s"] == 0.0
    from bench.layer_metrics import device_idle_pct

    class Run:
        trace = r
    assert device_idle_pct.read(Run, "device_idle_pct.train") is None


def test_recorded_trace_from_the_chip():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    r = T.reduce(rec)
    assert r["n_devices"] == 1
    assert r["busy_s"] == pytest.approx(0.01327735, rel=1e-6)
    assert r["window_s"] == pytest.approx(0.034030206, rel=1e-6)
    assert r["idle_gaps"][0][0] == "job_poll"
    runs, secs = T.module_seconds(r, "jit_tree_program")
    assert runs == 7 and secs == pytest.approx(22.896654594, rel=1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]

"""What the GLM cell on enum columns (airline_glm_train) brought to the
benchmark: the need of its roofline from the deployment's shapes, the stage
reader on a recorded span tree, the counter reader, and a whole run of
bench/run.py (dry run) with the program broken underneath, which must come
out not ``correct`` by the limit the fault must fail. The sound runs are
bench/tests/test_dry_run.py's (it takes every cell of BENCHMARK.json)."""

import json
import os
import sys
import types

import pytest

from bench.layer_metrics import glm_irls_iterations, glm_stage_s
from bench.roofline import glm_irls_enum, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg():
    with open(os.path.join(BENCH, "configs",
                           "airline_glm_binomial.json")) as f:
        return json.load(f)


def test_need_of_an_iteration_is_the_deployments_shapes():
    cfg = _cfg()
    rows = cfg["rows"]
    assert glm_irls_enum.stored_row_bytes(cfg) == 4 * 1 + 2 * 2 + 2 * 4
    need = glm_irls_enum.program_needed(cfg, rows, 3, iterations=6)
    assert need["bytes"] == 18 * rows * (16 + 1 + 8)
    assert need["flops"] == 18 * (2 * rows * 9 ** 2 + 4 * rows * 9)
    p = peaks.peak_for("TPU v5 lite")
    assert peaks.bound_by(need, p) == "bytes"
    step = glm_irls_enum.step_needed(cfg, rows, {"jobs_done": 3,
                                                 "iterations": 6})
    assert step == need
    # a dense Gram does 2 * 669^2 FLOP a row: 0.5% of it is needed
    dense = 2 * 669 ** 2 + 4 * 669
    assert (2 * 81 + 36) / dense < 0.001


def sp(name, start, end, sid, parent=None, **attrs):
    return {"name": name, "span_id": sid, "parent_id": parent,
            "start_ms": float(start), "end_ms": float(end),
            "ms": float(end - start), "attrs": attrs}


def glm_trace(t0=0.0):
    """ingress 0-10; job 5-1005 outlives it; design, irls (a compile
    inside it is nobody's entry) and metrics leave 20 ms of job."""
    return [sp("ingress", t0, t0 + 10, "i", path="/3/ModelBuilders/glm"),
            sp("job", t0 + 5, t0 + 1005, "j", "i"),
            sp("design", t0 + 10, t0 + 40, "d", "j"),
            sp("irls", t0 + 45, t0 + 845, "r", "j", iterations=6),
            sp("compile", t0 + 50, t0 + 60, "c", "r"),
            sp("metrics", t0 + 850, t0 + 1000, "m", "j", frame="train")]


def test_stages_add_up_to_the_job():
    traces = [glm_trace(), glm_trace(2000.0)]
    got = {k: glm_stage_s.stage_s(traces, k) for k in glm_stage_s.STAGES}
    assert got == {"design": 0.03, "irls": 0.8, "metrics": 0.15,
                   "other": 0.02}
    assert sum(got.values()) == pytest.approx(1.0)
    # a program without the spans: nothing to read, never 0
    bare = [[s for s in glm_trace() if s["name"] in ("ingress", "job")]]
    assert glm_stage_s.stage_s(bare, "irls") is None
    assert glm_stage_s.stage_s(bare, "other") == 1.0


def test_iterations_a_job_from_the_counter():
    run = types.SimpleNamespace(window={
        "jobs": [{}, {}, {}],
        "counters": {"h2o3_glm_iterations_total": {"": 18.0}}})
    assert glm_irls_iterations.read(run, "glm_irls_iterations") == 6.0
    run.window["counters"] = {}
    assert glm_irls_iterations.read(run, "glm_irls_iterations") is None


def _run(capsys, monkeypatch):
    from bench import run as bench_run

    monkeypatch.setattr(sys, "argv", [
        "bench/run.py", "--workload", "airline_glm_train", "--seed",
        "3200000023", "--seconds", "3", "--trace", "0", "--cpu-dry-run"])
    assert bench_run.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return out, {k for k, (v, lim) in out["compared"].items()
                 if not v <= lim}


def test_a_column_that_reads_its_levels_off_by_one(capsys, monkeypatch):
    """The design takes level l + 1 of the widest enum column for level l
    (and drops the last level, not the first): the fit is as good and every
    coefficient of that column carries its neighbour's name."""
    from h2o3_tpu.models import data_info

    real = data_info.DesignLayout.lane_levels

    def shifted(self):
        levels = real(self)
        i = max(range(len(levels)), key=lambda k: len(levels[k]))
        levels[i] = levels[i].copy()
        levels[i][levels[i] >= 0] -= 1
        return levels

    from h2o3_tpu.models import glm

    monkeypatch.setattr(data_info.DesignLayout, "lane_levels", shifted)
    glm._irls_fit.clear_cache()
    try:
        out, over = _run(capsys, monkeypatch)
    finally:
        glm._irls_fit.clear_cache()
    assert out["correct"] is False and "coef_gap" in over, out

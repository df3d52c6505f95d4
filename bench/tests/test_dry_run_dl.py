"""What the DeepLearning cell on enum columns (airline_dl_train) brought to
the benchmark: the need of its roofline from the deployment's shapes, the
stage reader on a recorded span tree, the roofline reader on a reduced
trace, and whole runs of bench/run.py (dry run): one sound, and one with the
program broken underneath, which must come out not ``correct`` by the
limits the fault must fail. bench/tests/test_dry_run.py runs the cell too
(it takes every cell of BENCHMARK.json)."""

import json
import os
import sys
import types

import pytest

from bench.layer_metrics import dl_stage_s, dl_train_roofline_pct
from bench.roofline import dl_train, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg():
    with open(os.path.join(BENCH, "configs", "airline_dl_200x200.json")) as f:
        return json.load(f)


def test_need_of_a_step_and_a_pass_is_the_deployments_shapes():
    cfg = _cfg()
    rows = cfg["rows"]
    d = dl_train.distinct_levels(cfg, 32)
    # 12, 31 and 7 uniform levels give 11.3 + 20.1 + 6.9 of 32 draws; the
    # Zipf-1 carriers and airports the rest
    assert 95 < d < 100
    step = dl_train.step_need(cfg)
    changed = d * 200 + 3 * 200 + 201 * 200 + 201 * 2
    assert step["bytes"] == pytest.approx(28 * changed + 32 * (16 + 5))
    assert step["flops"] == 6 * 32 * (8 * 200 + 200 * 200 + 200 * 2)
    p = peaks.peak_for("TPU v5 lite")
    assert peaks.bound_by(step, p) == "bytes"
    assert 1.6e6 < step["bytes"] < 1.8e6
    assert 2.0e-6 < peaks.least_seconds(step, p) < 2.2e-6
    run_need = dl_train.program_needed(cfg, rows, 2, steps_a_run=500.0)
    assert run_need == {k: 1000 * v for k, v in step.items()}
    per_job = dl_train.steps_a_job(cfg, rows)
    assert per_job == round(cfg["params"]["epochs"] * rows / 32) == 225_000
    passes = dl_train.pass_need(cfg, rows)
    assert passes["bytes"] == rows * (16 + 5)
    # the window's steps and runs from the program's counters, the steps
    # from the epochs without them
    work = {"jobs_done": 3,
            "counters": {"h2o3_dl_steps_total": {"": 1234.0},
                         "h2o3_dl_dispatches_total": {"": 4.0}}}
    assert dl_train.window_steps(cfg, rows, work) == 1234
    assert dl_train.steps_a_run(work) == 308.5
    assert dl_train.window_steps(cfg, rows, {"jobs_done": 3}) == 3 * per_job
    assert dl_train.steps_a_run({"jobs_done": 3}) is None
    need = dl_train.step_needed(cfg, rows, work)
    assert need["bytes"] == pytest.approx(1234 * step["bytes"]
                                          + 6 * passes["bytes"])


def sp(name, start, end, sid, parent=None, **attrs):
    return {"name": name, "span_id": sid, "parent_id": parent,
            "start_ms": float(start), "end_ms": float(end),
            "ms": float(end - start), "attrs": attrs}


def dl_trace(t0=0.0, path="/3/ModelBuilders/deeplearning", epochs=800):
    """ingress 0-10; job 5-1005 outlives it; design, epochs (a compile
    inside it is nobody's entry) and metrics leave 20 ms of job."""
    return [sp("ingress", t0, t0 + 10, f"i{t0}", path=path),
            sp("job", t0 + 5, t0 + 1005, f"j{t0}", f"i{t0}"),
            sp("design", t0 + 10, t0 + 40, f"d{t0}", f"j{t0}"),
            sp("epochs", t0 + 45, t0 + 45 + epochs, f"e{t0}", f"j{t0}"),
            sp("compile", t0 + 50, t0 + 60, f"c{t0}", f"e{t0}"),
            sp("metrics", t0 + 850, t0 + 1000, f"m{t0}", f"j{t0}",
               frame="train")]


def test_stages_add_up_to_the_job():
    traces = [dl_trace(), dl_trace(2000.0)]
    got = {k: dl_stage_s.stage_s(traces, k) for k in dl_stage_s.STAGES}
    assert got == pytest.approx({"design": 0.03, "epochs": 0.8,
                                 "metrics": 0.15, "other": 0.02})
    assert sum(got.values()) == pytest.approx(1.0)
    bare = [[s for s in dl_trace() if s["name"] in ("ingress", "job")]]
    assert dl_stage_s.stage_s(bare, "epochs") is None


def test_the_requests_after_the_window_are_left_out():
    """Newest first, as the span store lists them: the check's short job
    and its predictions came after the window's two jobs."""
    newest = [dl_trace(9000.0, epochs=20),
              dl_trace(8000.0, path="/3/Predictions/models/m/frames/f"),
              dl_trace(2000.0), dl_trace(0.0),
              dl_trace(-3000.0, epochs=100)]           # the warm-up job
    run = types.SimpleNamespace(
        window={"jobs": [{}, {}], "posts_after_window": 2},
        system=types.SimpleNamespace(spans=lambda root: newest))
    assert dl_stage_s.read(run, "dl_stage_s.epochs") == pytest.approx(0.8)
    run.window["posts_after_window"] = 0
    assert dl_stage_s.read(run, "dl_stage_s.epochs") \
        == pytest.approx((0.02 + 0.8) / 2)


def test_roofline_share_from_the_training_programs_runs():
    cfg = _cfg()
    p = peaks.peak_for("TPU v5 lite")
    # seven whole runs of 2,048 steps in the slice; over the window the
    # counters give 225,000 steps in 110 runs
    secs = 0.53
    run = types.SimpleNamespace(
        cfg=cfg, rows=cfg["rows"], peak=p,
        trace={"modules": {"jit__dl_train_steps": (7, secs),
                           "jit__dl_loss_pass": (1, 1.0)}},
        window={"jobs_done": 1,
                "counters": {"h2o3_dl_steps_total": {"": 225_000.0},
                             "h2o3_dl_dispatches_total": {"": 110.0}}})
    got = dl_train_roofline_pct.read(run, "dl_train_roofline_pct")
    want = 100.0 * peaks.least_seconds(dl_train.step_need(cfg), p) \
        * 7 * 225_000 / 110 / secs
    assert got == pytest.approx(want)
    assert 1 < got < 10
    run.window["counters"] = {}
    assert dl_train_roofline_pct.read(run, "dl_train_roofline_pct") is None
    run.window["counters"] = {"h2o3_dl_steps_total": {"": 225_000.0},
                              "h2o3_dl_dispatches_total": {"": 110.0}}
    run.trace = {"modules": {"jit__dl_loss_pass": (3, 1.0)}}
    assert dl_train_roofline_pct.read(run, "dl_train_roofline_pct") is None
    run.trace = None
    assert dl_train_roofline_pct.read(run, "dl_train_roofline_pct") is None


def _run(capsys, monkeypatch, seed):
    from bench import run as bench_run

    monkeypatch.setattr(sys, "argv", [
        "bench/run.py", "--workload", "airline_dl_train", "--seed",
        str(seed), "--seconds", "3", "--trace", "0", "--cpu-dry-run"])
    assert bench_run.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return out, {k for k, (v, lim) in out["compared"].items()
                 if not v <= lim}


def _clear():
    from h2o3_tpu.models import deeplearning as dl_mod

    dl_mod._dl_train_steps.clear_cache()
    dl_mod._dl_pass.cache_clear()


def test_a_sound_run_and_a_column_read_off_by_one(capsys, monkeypatch):
    """A sound run is correct with every limit compared. Then the program
    takes level l + 1 of the widest enum column for level l: the trained
    weights sit on the wrong rows of W1 and its predictions are another
    model's."""
    out, over = _run(capsys, monkeypatch, 3800000023)
    assert out["correct"] is True and not over, out
    assert set(out["compared"]) == set(_cfg()["limits"])
    from h2o3_tpu.models import data_info

    real = data_info.DesignLayout.lane_levels

    def shifted(self):
        levels = real(self)
        i = max(range(len(levels)), key=lambda k: len(levels[k]))
        levels[i] = levels[i].copy()
        levels[i][levels[i] >= 0] = (levels[i][levels[i] >= 0] + 1) \
            % self.cards[i]
        return levels

    monkeypatch.setattr(data_info.DesignLayout, "lane_levels", shifted)
    _clear()
    try:
        out, over = _run(capsys, monkeypatch, 3800000023)
    finally:
        _clear()
    assert out["correct"] is False and {"weight_gap", "prob_gap"} <= over, \
        out

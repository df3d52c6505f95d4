"""What the DeepLearning cell on an all-numeric frame (mnist_dl_train)
brought to the benchmark: the needs of its two rooflines from the
deployment's shapes, the two roofline readers on a raw device trace, the data
recipe's constant columns, and whole runs of bench/run.py (dry run): one
sound, and one with the program's max_w2 rescale skipped underneath, which
must come out not ``correct`` by the short job's weights.
bench/tests/test_dry_run.py runs the cell too (it takes every cell of
BENCHMARK.json)."""

import json
import os
import sys
import types

import numpy as np
import pytest

from bench.harness import data_mnist
from bench.layer_metrics import (dl_dense_pass_roofline_pct,
                                 dl_dense_train_roofline_pct)
from bench.roofline import dl_dense_train, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg():
    with open(os.path.join(BENCH, "configs",
                           "mnist8m_dl_1024x1024x2048.json")) as f:
        return json.load(f)


def test_need_of_a_step_and_a_pass_is_the_deployments_shapes():
    cfg = _cfg()
    rows = cfg["rows"]
    assert dl_dense_train.parameters(cfg) == 3_904_522
    step = dl_dense_train.step_need(cfg)
    assert step["flops"] == 6 * 32 * 3_900_416
    assert step["bytes"] == 28 * 3_904_522 + 32 * (784 * 4 + 5)
    p = peaks.peak_for("TPU v5 lite")
    assert peaks.bound_by(step, p) == "bytes"
    assert 133e-6 < peaks.least_seconds(step, p) < 135e-6
    one = dl_dense_train.pass_need(cfg, rows)
    assert one["flops"] == 2 * rows * 3_900_416
    assert one["bytes"] == rows * (784 * 4 + 5)
    assert peaks.bound_by(one, p) == "flops"
    assert 0.079 < peaks.least_seconds(one, p) < 0.081
    assert dl_dense_train.pass_needed(cfg, rows, 3) == \
        {k: 3 * v for k, v in one.items()}
    work = {"jobs_done": 2,
            "counters": {"h2o3_dl_steps_total": {"": 1000.0},
                         "h2o3_dl_dispatches_total": {"": 4.0}}}
    need = dl_dense_train.step_needed(cfg, rows, work)
    assert need["flops"] == pytest.approx(1000 * step["flops"]
                                          + 4 * one["flops"])


US = 1_000      # ns


def _slice():
    """A raw trace of one device: a training run the slice cut at its
    start (10 steps), a whole one (20 steps), a whole loss pass and a
    predictions pass the slice cut at its end. A step is ten body
    operations of 100 us; a run adds its while loop and one copy."""
    mods, ops = [], []
    t = 0

    def train_run(steps):
        nonlocal t
        t0 = t
        ops.append(["%while.4 = (...) while(...)", t0, steps * 1000 * US])
        for _ in range(steps):
            for j in range(10):
                ops.append([f"%fusion.{j} = f32[1024,2048] fusion(...)", t,
                            100 * US])
                t += 100 * US
        ops.append(["%copy.1 = f32[717,1024] copy(...)", t, 5 * US])
        t += 5 * US
        mods.append(["jit__dl_train_steps(1)", t0, t - t0])

    def pass_run(name, secs):
        nonlocal t
        ops.append(["%while.2 = (...) while(...)", t, int(secs * 1e9)])
        mods.append([name, t, int(secs * 1e9)])
        t += int(secs * 1e9)

    train_run(10)
    train_run(20)
    ops.append(["%fusion.9 = f32[] fusion(...)", t, 20 * US])
    t += 20 * US
    pass_run("jit__dl_loss_pass(2)", 1.2)
    pass_run("jit__dl_predict_pass(3)", 0.7)
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Modules", "events": mods},
                   {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": [["x", 0, 10]]}]}]}


def test_roofline_shares_of_the_steps_and_the_passes():
    """Both shares from the device trace alone: the steps are the training
    loop's iterations inside the slice, counted by its body's operations
    over the runs' seconds there (the first run cut, so a count of runs
    says nothing); the passes are those the slice holds whole."""
    from bench.harness import trace_runs

    cfg = _cfg()
    p = peaks.peak_for("TPU v5 lite")
    raw = _slice()
    found = trace_runs.programs_in_slice(raw, dict(cfg["programs"],
                                                   none="jit__no_such"))
    assert found["none"] is None
    train = found["dl_dense_train"]
    step_s = 10 * 100e-6
    assert train["runs"] == 2 and train["iterations"] == 30
    assert train["seconds"] == pytest.approx(30 * step_s + 10e-6)
    assert train["whole_runs"] == 1
    passes = found["dl_dense_pass"]
    assert passes["runs"] == 2 and passes["whole_runs"] == 1
    assert passes["whole_seconds"] == pytest.approx(1.2)
    # a line's events may come as a stream, read once
    streamed = {"planes": [dict(p, lines=[dict(ln, events=iter(ln["events"]))
                                          for ln in p["lines"]])
                           for p in raw["planes"]]}
    assert trace_runs.programs_in_slice(streamed, cfg["programs"]) == \
        {k: found[k] for k in cfg["programs"]}
    run = types.SimpleNamespace(
        cfg=cfg, rows=cfg["rows"], peak=p, trace={"modules": {}},
        window={"slice_programs": {"dl_dense_train": train,
                                   "dl_dense_pass": passes}})
    got = dl_dense_train_roofline_pct.read(run, "dl_dense_train_roofline_pct")
    least = peaks.least_seconds(dl_dense_train.step_need(cfg), p)
    assert got == pytest.approx(100.0 * least * 30 / train["seconds"])
    assert 0 < got < 100
    share = dl_dense_pass_roofline_pct.read(run, "dl_dense_pass_roofline_pct")
    one = peaks.least_seconds(dl_dense_train.pass_need(cfg, cfg["rows"]), p)
    assert share == pytest.approx(100.0 * one / 1.2)
    assert 0 < share < 100
    run.window["slice_programs"]["dl_dense_pass"] = dict(passes,
                                                         whole_runs=0)
    assert dl_dense_pass_roofline_pct.read(run, "x") is None
    run.window = {}
    assert dl_dense_pass_roofline_pct.read(run, "x") is None
    assert dl_dense_train_roofline_pct.read(run, "x") is None
    run.window = {"slice_programs": {"dl_dense_train": train,
                                     "dl_dense_pass": passes}}
    run.trace = None
    assert dl_dense_pass_roofline_pct.read(run, "x") is None
    assert dl_dense_train_roofline_pct.read(run, "x") is None


def test_the_recipe_keeps_67_dead_pixels_and_nineteen_percent_ink():
    out = data_mnist.device_columns(4_000_000_003, 2048)
    X = np.stack([np.asarray(c) for c in out[:-1]], axis=1)
    dead = data_mnist.dead_pixels()
    assert len(dead) == 67 and not X[:, dead].any()
    live = np.setdiff1d(np.arange(784), dead)
    assert (X[:, live].max(axis=0) > 0).all()
    assert 0.18 < (X > 0).mean() < 0.20
    assert set(np.unique(X)) <= set(range(256))
    assert sorted(np.unique(np.asarray(out[-1]))) == list(range(10))
    again = data_mnist.device_columns(4_000_000_003, 2048)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(out, again))


def _run(capsys, monkeypatch, seed):
    from bench import run as bench_run

    monkeypatch.setattr(sys, "argv", [
        "bench/run.py", "--workload", "mnist_dl_train", "--seed",
        str(seed), "--seconds", "3", "--trace", "0", "--cpu-dry-run"])
    assert bench_run.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return out, {k for k, (v, lim) in out["compared"].items()
                 if not v <= lim}


def _clear():
    from h2o3_tpu.models import deeplearning as dl_mod

    dl_mod._dl_train_steps.clear_cache()
    dl_mod._dl_pass.cache_clear()


def test_a_sound_run_and_max_w2_skipped(capsys, monkeypatch):
    """A sound run is correct with every limit compared, its design 717
    wide. Then the program skips the max_w2 rescale: the short job's
    weights, which a cap of 0.5 holds from the first step, part from the
    replay's."""
    out, over = _run(capsys, monkeypatch, 4000000023)
    assert out["correct"] is True and not over, out
    assert set(out["compared"]) == set(_cfg()["limits"])
    assert out["info"]["window"]["design_inputs"] == 717
    assert out["info"]["window"]["design_const_cols_dropped"] == 67
    from h2o3_tpu.models import deeplearning as dl_mod

    monkeypatch.setattr(dl_mod, "_max_w2",
                        lambda max_w2, params: (params, 0))
    _clear()
    try:
        out, over = _run(capsys, monkeypatch, 4000000023)
    finally:
        _clear()
    assert out["correct"] is False and "weight_gap" in over, out


def test_a_program_that_drops_a_parameter_fails_before_any_data():
    """``/3/ModelBuilders`` drops an unknown parameter with a warning and
    would train another network; the driver asks the validation route
    first and stops the run there (a program without ``max_w2`` and
    ``ignore_const_cols`` would train 784 inputs and no cap)."""
    from bench.drivers import train_jobs_dl_dense

    class Rest:
        def __init__(self, unknown):
            self.unknown = unknown

        def request(self, method, path, data=None, query=None):
            assert path == "/3/ModelBuilders/deeplearning/parameters"
            assert data["max_w2"] == 10.0
            return 200, {"messages": [
                {"field_name": k, "message": f"unknown parameter {k}"}
                for k in self.unknown]}

    cfg = _cfg()
    run = types.SimpleNamespace(cfg=cfg, seed=4_000_000_001,
                                mix={"model_id": "bench_model"},
                                rest=Rest(["max_w2", "ignore_const_cols"]))
    with pytest.raises(RuntimeError, match="ignore_const_cols"):
        train_jobs_dl_dense.refuse_unknown_parameters(run)
    run.rest = Rest([])
    train_jobs_dl_dense.refuse_unknown_parameters(run)


def test_the_result_line_keeps_the_op_names_short():
    """A traced run's breakdown is the reduced trace's own list, printed
    after the check: the check cuts each name (HLO text, about 40 KB for a
    loop that carries the 717 columns) to its head, in that list."""
    from bench.drivers import train_jobs_dl_dense as driver

    head = "%while.2 = (s32[]{:T(128)}, f32[2025000]{0:T(1024)}"
    ops = [[head + ", f32[2025000]{0:T(1024)}" * 1500, 1.2],
           ["%fusion.55 = f32[1024,2048]", 0.27]]
    run = types.SimpleNamespace(trace={"device_ops": ops,
                                       "idle_gaps": [["job_post", 0.03]]},
                                window={"failed": 1})
    breakdown = {"device_ops": run.trace["device_ops"]}
    with pytest.raises(RuntimeError, match="did not end DONE"):
        driver.check(run)
    assert [len(n) for n, _s in breakdown["device_ops"]] == [
        driver.OP_NAME_CHARS, len("%fusion.55 = f32[1024,2048]")]
    assert breakdown["device_ops"][0][0].startswith(head)
    assert len(json.dumps(breakdown)) < 1000
    run.trace = None                # an untraced run has nothing to cut
    driver.trim_op_names(run)

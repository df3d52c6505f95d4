"""The enum cell (airline_gbm_train) as the driver would start it, at 20,000
rows on the CPU (a process of its own); its two counter readers on a
recorded ``GET /3/Metrics`` reply; the driver's deadline."""

import json
import os
import subprocess
import sys
import types

import pytest

from bench.drivers import train_jobs_enum
from bench.layer_metrics import forest_walk_enum_pct, tree_split_enum_pct

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
CELL = "airline_gbm_train"


def _bench(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=900)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_dry_run(trace):
    p = _bench("--workload", CELL, "--seed", "3000000029", "--seconds", "4",
               "--trace", trace, "--cpu-dry-run")
    assert p.returncode == 0, p.stderr[-3000:]
    assert "platform=cpu DRY RUN" in p.stderr
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["compared"]
    assert out["device"]["platform"] == "cpu" and out["failed"] == 0
    assert out["info"]["window_compiles"] == 0
    with open(os.path.join(ROOT, "bench", "configs",
                           "airline_gbm_d10.json")) as f:
        assert set(out["compared"]) == set(json.load(f)["limits"])
    if trace == "0":
        assert set(out["metrics"]) == {"train_rows_per_s", "setup_s"}
    else:
        m = out["metrics"]
        assert m["tree_split_enum_pct"]["value"] > 50
        assert m["forest_walk_enum_pct"]["value"] > 50
        stages = sum(m[f"train_stage_s.{s}"]["value"] for s in
                     ("bin", "trees", "assemble", "metrics", "other"))
        assert 0 < stages <= out["info"]["jobs"][-1]["seconds"]


def test_the_recipe_is_what_the_configuration_states():
    from bench.harness import data_airline as recipe

    with open(os.path.join(ROOT, "bench", "configs",
                           "airline_gbm_d10.json")) as f:
        cfg = json.load(f)
    assert [(c["name"], c["type"], c["levels"]) for c in cfg["columns"]] \
        == list(recipe.COLUMNS)
    assert recipe.top_share("Origin") == pytest.approx(0.466, abs=1e-3)
    assert recipe.top_share("UniqueCarrier") == pytest.approx(0.794, abs=1e-3)
    assert recipe.positives(7) == pytest.approx(0.19, abs=0.005)
    cols = recipe.device_columns(3000000041, 20000)
    assert [str(c.dtype) for c in cols] == ["int8"] * 4 + ["int16"] * 2 \
        + ["float32"] * 2 + ["int8"]
    again = recipe.device_columns(3000000041, 20000)
    assert all((a == b).all() or str(a.dtype) == "float32"
               for a, b in zip(cols, again))


class _Rest:
    """A recorded reply of GET /3/Metrics?format=json, cut to three series."""

    def __init__(self, series):
        self.series = series

    def request(self, method, path, data=None, query=None):
        assert (method, path, query) == ("GET", "/3/Metrics",
                                         {"format": "json"})
        return 200, {"series": self.series}


def _series(name, label, values):
    return {"name": name, "type": "counter",
            "samples": [{"labels": {label: k}, "value": v}
                        for k, v in values.items()]}


BEFORE = [_series("h2o3_tree_splits_total", "kind",
                  {"enum": 700.0, "numeric": 30.0}),
          _series("h2o3_forest_walk_total", "form", {"select+cat": 2.0}),
          {"name": "h2o3_other_total", "type": "counter",
           "samples": [{"labels": {}, "value": 5.0}]}]
AFTER = [_series("h2o3_tree_splits_total", "kind",
                 {"enum": 1400.0, "numeric": 50.0}),
         _series("h2o3_forest_walk_total", "form",
                 {"select+cat": 5.0, "select": 1.0})]


def _run(counters):
    return types.SimpleNamespace(window={"counters": counters})


def test_counter_readers_on_a_recorded_reply():
    moved = train_jobs_enum.counters_between(
        train_jobs_enum.read_counters(_Rest(BEFORE)),
        train_jobs_enum.read_counters(_Rest(AFTER)))
    assert moved == {"h2o3_tree_splits_total": {"enum": 700.0,
                                                "numeric": 20.0},
                     "h2o3_forest_walk_total": {"select+cat": 3.0,
                                                "select": 1.0}}
    assert tree_split_enum_pct.read(_run(moved), "tree_split_enum_pct") \
        == pytest.approx(100 * 700 / 720)
    assert forest_walk_enum_pct.read(_run(moved), "forest_walk_enum_pct") \
        == pytest.approx(75.0)


@pytest.mark.parametrize("counters", [None, {}, {"h2o3_tree_splits_total": {},
                                                 "h2o3_forest_walk_total": {}}])
def test_a_program_without_the_counters_reads_nothing_not_zero(counters):
    """The parent's program has no h2o3_tree_splits_total: the reader gives
    None and the harness leaves the metric out of the line."""
    assert tree_split_enum_pct.read(_run(counters), "x") is None
    assert forest_walk_enum_pct.read(_run(counters), "x") is None
    assert train_jobs_enum.read_counters(_Rest([])) == {}


def test_warm_up_job_that_never_ends_fails_the_run(monkeypatch):
    """Past the deadline the job is cancelled and set-up raises."""
    calls = []

    class Rest:
        def request(self, method, path, data=None, query=None):
            calls.append((method, path))
            return 200, {"job": {"key": {"name": "j1"}}}

        def __call__(self, method, path):
            return {"jobs": [{"status": "RUNNING", "dest": {"name": "m"}}]}

    import contextlib

    run = types.SimpleNamespace(
        rest=Rest(), cfg={"algo": "gbm", "params": {}}, seed=1,
        mix={"model_id": "m"}, state={},
        timed=lambda name: contextlib.nullcontext())
    monkeypatch.setattr(train_jobs_enum, "WARM_UP_DEADLINE_S", 0.2)
    monkeypatch.setattr(train_jobs_enum.threading, "Timer",
                        lambda *a, **k: types.SimpleNamespace(
                            daemon=True, start=lambda: None))
    with pytest.raises(RuntimeError, match="not DONE in"):
        train_jobs_enum._warm_up_job(run)
    assert ("POST", "/3/Jobs/j1/cancel") in calls

"""tree_psum_mb_per_job over recorded span trees: the `psum_bytes` of the
window's `trees` and `metrics` spans over its jobs (PR 34's program), and
without the attribute (the parent's), where the reader gives None and the
line leaves it out; the pure part of psum_on_chip.py on a hand-made
capture."""

import json
import os

from bench.layer_metrics import tree_psum_mb_per_job
from bench.tests import psum_on_chip
from bench.tests.test_span_readers import run_with, sp, train_trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
NAME = "tree_psum_mb_per_job"
A_TREE = 4 * 896 * 3 * 1023 + 4 * 2048 * 4 + 8     # airline, depth 10
WALK = 4 * (3 + 2 * 400)


def job(t0, trees=None, metrics=None):
    tr = train_trace(t0=t0)
    tr[5] = sp("trees", t0 + 110, t0 + 610, "t", "j", ntrees=2,
               **(trees or {}))
    tr[7] = sp("metrics", t0 + 641, t0 + 941, "m1", "j", frame="train",
               **(metrics or {}))
    return tr


def test_megabytes_a_job_over_the_windows_spans():
    other = [sp("ingress", 0, 1, "g", path="/3/Models/bench_model")]
    four = dict(trees={"shards": 4, "psum_bytes": 2 * A_TREE},
                metrics={"shards": 4, "psum_bytes": WALK})
    # newest first; the warm-up job is older than the window's two
    jobs = [job(9000.0, **four), job(5000.0, **four),
            job(0.0, trees={"shards": 4, "psum_bytes": 10 ** 9})]
    run = run_with([other] + jobs, jobs=[{}, {}])
    assert tree_psum_mb_per_job.read(run, NAME) == \
        (2 * A_TREE + WALK) / 1e6 == 22.067356
    # a mesh of one device: the attribute is there and reads 0
    one = dict(trees={"shards": 1, "psum_bytes": 0},
               metrics={"shards": 1, "psum_bytes": 0})
    assert tree_psum_mb_per_job.read(
        run_with([job(0.0, **one)], jobs=[{}]), NAME) == 0.0


def test_a_program_without_the_attribute_reports_nothing():
    run = run_with([train_trace()], jobs=[{}])
    assert tree_psum_mb_per_job.read(run, NAME) is None
    assert tree_psum_mb_per_job.read(run_with([], jobs=[]), NAME) is None


def test_the_manifest_lists_it_for_the_four_chip_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "MB", "better": "lower",
                     "source": "program_span", "layer": "tree_program",
                     "moves": "train_rows_per_s",
                     "workloads": ["airline_gbm_train_4chip"]}
    cell, = [w for w in manifest["workloads"]
             if w["name"] == "airline_gbm_train_4chip"]
    assert cell["chips"] == 4
    assert cell["traffic"] == "train_jobs_enum_sharded"


def test_collective_seconds_a_device_a_program_and_a_site():
    """Two devices, one run of the tree program each: a scoped all-reduce
    pair, an unscoped one counted by its place, own time under a while."""
    us = 1000

    def plane(wait):
        ops = [["while.1", 0, 100 * us, {}],
               ["fusion.2", 1 * us, 40 * us,
                {"tf_op": "jit(tree_program)/level3/hist/dot"}],
               ["all-reduce-start.1", 50 * us, 2 * us,
                {"tf_op": "jit(tree_program)/level3/hist/psum/psum"}],
               ["all-reduce-done.1", 52 * us, wait * us,
                {"tf_op": "jit(tree_program)/level3/hist/psum/psum"}],
               ["all-reduce.7", 90 * us, 5 * us, {}],
               ["fusion.9", 200 * us, 10 * us, {}]]
        return {"ops": ops, "modules": [["jit_tree_program(3)", 0, 100 * us],
                                        ["jit_post(4)", 200 * us, 10 * us]]}

    fast, slow = psum_on_chip.by_device([plane(8), plane(1)])
    rec = fast["programs"]["jit_tree_program"]
    assert set(fast["programs"]) == {"jit_tree_program"}
    assert rec["runs"] == 1 and abs(rec["seconds"] - 100e-6) < 1e-12
    assert abs(rec["sites"]["level3/hist/psum"] - 10e-6) < 1e-12
    assert abs(rec["sites"]["#1"] - 5e-6) < 1e-12
    assert abs(rec["collective_s"] - 15e-6) < 1e-12
    assert abs(fast["busy_s"] - 110e-6) < 1e-12
    assert abs(slow["programs"]["jit_tree_program"]["collective_s"]
               - 8e-6) < 1e-12

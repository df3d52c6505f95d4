"""tree_route_select_pct over recorded span trees: with the `trees` span's
`route_levels` / `route_gather_levels` (PR 29's program) and without them
(the parent's), where the reader gives None and the line leaves it out."""

import json
import os

from bench.layer_metrics import tree_route_select_pct
from bench.tests.test_span_readers import run_with, sp, train_trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def job(t0, **trees_attrs):
    tr = train_trace(t0=t0)
    tr[5] = sp("trees", t0 + 110, t0 + 610, "t", "j", ntrees=2,
               **trees_attrs)
    return tr


def test_share_over_the_windows_trees_spans():
    other = [sp("ingress", 0, 1, "g", path="/3/Models/bench_model")]
    # newest first; two depth-10 trees at maxB 301 a job: level 9 gathers
    jobs = [job(9000.0, route_levels=20, route_gather_levels=2),
            job(5000.0, route_levels=20, route_gather_levels=2),
            job(0.0, route_levels=25, route_gather_levels=25)]   # warm-up
    run = run_with([other] + jobs, jobs=[{}, {}])
    assert tree_route_select_pct.read(run, "tree_route_select_pct") == 90.0
    all_select = [job(0.0, route_levels=25, route_gather_levels=0)]
    assert tree_route_select_pct.read(
        run_with(all_select, jobs=[{}]), "tree_route_select_pct") == 100.0


def test_a_program_without_the_attributes_reports_nothing():
    run = run_with([train_trace()], jobs=[{}])
    assert tree_route_select_pct.read(run, "tree_route_select_pct") is None
    bare = [[sp("ingress", 0, 10, "i", path="/3/ModelBuilders/gbm")]]
    assert tree_route_select_pct.read(
        run_with(bare, jobs=[{}]), "tree_route_select_pct") is None
    assert tree_route_select_pct.read(
        run_with([], jobs=[]), "tree_route_select_pct") is None


def test_the_manifest_lists_it_for_both_training_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"]
                  if m["name"] == "tree_route_select_pct"]
    assert entry == {"name": "tree_route_select_pct", "unit": "%",
                     "better": "higher", "source": "program_span",
                     "layer": "tree_program", "moves": "train_rows_per_s",
                     "workloads": ["gbm_train", "airline_gbm_train"]}

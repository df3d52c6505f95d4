"""Drive a whole run of bench/run.py on the enum cell (dry run) with the
timed path broken underneath, once for each fault an enum cell can have, and
see ``correct`` come out false by the limit that fault must fail. The sound
run is bench/tests/test_enum_cell.py's."""

import json
import sys

import numpy as np
import pytest


def _run(capsys, monkeypatch):
    from bench import run as bench_run

    monkeypatch.setattr(sys, "argv", [
        "bench/run.py", "--workload", "airline_gbm_train", "--seed",
        "3000000023", "--seconds", "3", "--trace", "0", "--cpu-dry-run"])
    assert bench_run.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return out, {k for k, (v, lim) in out["compared"].items()
                 if not v <= lim}


def test_levels_taken_in_code_order(capsys, monkeypatch):
    """The split search orders an enum feature's levels by their code, as
    it orders a numeric feature's bins: a subset is then a run of codes."""
    from h2o3_tpu.models.tree import device_tree

    real = device_tree._search_level

    def by_code(hist, *, is_cat, **kw):
        return real(hist, is_cat=tuple(False for _ in is_cat), **kw)

    device_tree._grow_fn.cache_clear()
    monkeypatch.setattr(device_tree, "_search_level", by_code)
    try:
        out, over = _run(capsys, monkeypatch)
    finally:
        device_tree._grow_fn.cache_clear()
    assert out["correct"] is False, out
    assert {"split_gain_loss", "split_gain_gap"} <= over, out


def _patched_trees(monkeypatch, bend):
    """Every assembled HostTree passes through ``bend`` before the forest
    is built from it."""
    from h2o3_tpu.models.tree import device_tree

    real = device_tree.host_tree_from_packed

    def broken(*a, **kw):
        tree = real(*a, **kw)
        bend(tree)
        return tree

    monkeypatch.setattr(device_tree, "host_tree_from_packed", broken)


def test_missing_side_flipped(capsys, monkeypatch):
    """On the recipe's variant with missing rows: the cell's frame has none,
    as the source's file has none, so there the side says nothing."""
    from bench.harness import data_airline

    monkeypatch.setattr(data_airline, "NA_SHARE", 1.0 / 256)

    def bend(tree):
        for n in tree.nodes:
            if n.split is not None:
                n.split.na_left = not n.split.na_left

    _patched_trees(monkeypatch, bend)
    out, over = _run(capsys, monkeypatch)
    assert out["correct"] is False and "cover_gap" in over, out


def test_one_level_dropped_from_a_left_set(capsys, monkeypatch):
    def bend(tree):
        for n in tree.nodes:
            if n.split is not None and n.split.is_cat:
                on = np.nonzero(n.split.left_bins)[0]
                if len(on):
                    n.split.left_bins[on[0]] = False

    _patched_trees(monkeypatch, bend)
    out, over = _run(capsys, monkeypatch)
    assert out["correct"] is False and "cover_gap" in over, out


def test_step_that_returns_its_state_unchanged(capsys, monkeypatch):
    from h2o3_tpu.models.tree import shared_tree

    real = shared_tree._post_fn

    def broken(builder, clip):
        fn = real(builder, clip)

        def post(leaf4, row_leaf, f, lr):
            gamma, _f_new = fn(leaf4, row_leaf, f, lr)
            return gamma, f
        return post

    monkeypatch.setattr(shared_tree, "_post_fn", broken)
    out, over = _run(capsys, monkeypatch)
    assert out["correct"] is False
    assert over & {"leaf_gap", "logloss_gap"}, out


def test_trees_cut_at_depth_three(capsys, monkeypatch):
    """The fit grows depth-3 trees whatever ``max_depth`` the job asked for:
    every leaf, cover and the log loss agree with the forest it reports,
    and only a leaf that stops where a split still pays says so."""
    from h2o3_tpu.models.tree import shared_tree

    real = shared_tree.SharedTree._fit_single

    def shallow(self, *a, **kw):
        self.params = dict(self.params, max_depth=3)
        return real(self, *a, **kw)

    monkeypatch.setattr(shared_tree.SharedTree, "_fit_single", shallow)
    out, over = _run(capsys, monkeypatch)
    assert out["correct"] is False and over == {"split_rule_breaks"}, out

import json
import os

import pytest

from bench.roofline import fused_score, glm_irls, peaks, tree_grow

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_peaks_table_and_unknown_kind():
    p = peaks.peak_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak_for("cpu")


def test_tree_grow_at_the_cell_shape():
    cfg = _cfg("higgs_gbm_d5")
    rows = cfg["rows"]
    one = tree_grow.program_needed(cfg, rows, 1)
    assert one["bytes"] == 5 * rows * (28 + 4 + 8) + rows * 8
    assert one["flops"] == 5 * 3 * rows * 28
    p = peaks.peak_for("TPU v5 lite")
    assert peaks.bound_by(one, p) == "bytes"
    assert peaks.least_seconds(one, p) == pytest.approx(one["bytes"] / 819e9)
    job = tree_grow.step_needed(cfg, rows, {"jobs_done": 2})
    n = cfg["params"]["ntrees"]
    assert job["bytes"] == 2 * (n * one["bytes"] + rows * 28 * 5)


def test_glm_irls_at_the_cell_shape():
    cfg = _cfg("higgs_glm_binomial")
    rows = cfg["rows"]
    need = glm_irls.program_needed(cfg, rows, 3, iterations=6)
    assert need["bytes"] == 18 * rows * (28 * 4 + 4)
    assert need["flops"] == 18 * (2 * rows * 29 ** 2 + 4 * rows * 29)


def test_fused_score_counts_one_read_and_one_write():
    cfg = _cfg("higgs_gbm_d5")
    need = fused_score.step_needed(cfg, 0, {"rows_scored": 1_000_000})
    assert need["bytes"] == 1_000_000 * (28 * 4 + 12)
    assert need["flops"] == 2 * 1_000_000 * cfg["params"]["ntrees"] * 5

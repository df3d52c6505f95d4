"""Drive a whole run of bench/run.py (dry run: no look for a chip) with the
timed path broken underneath, and see ``correct`` come out false — once for
each fault a cell can have. The sound run comes out true."""

import json
import sys

import pytest


def _run(capsys, monkeypatch, workload, seconds="3"):
    from bench import run as bench_run

    monkeypatch.setattr(sys, "argv", [
        "bench/run.py", "--workload", workload, "--seed", "3000000023",
        "--seconds", seconds, "--trace", "0", "--cpu-dry-run"])
    assert bench_run.main() == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(line)


def test_sound_train_run_is_correct(capsys, monkeypatch):
    out = _run(capsys, monkeypatch, "gbm_train")
    assert out["correct"] is True, out["compared"]
    assert list(out)[-1] == "compared"
    assert {"train_rows_per_s", "setup_s"} <= set(out["metrics"])


def test_step_that_returns_its_state_unchanged(capsys, monkeypatch):
    """The margin update after each tree is dropped: every tree is the
    first one."""
    from h2o3_tpu.models.tree import shared_tree

    real = shared_tree._post_fn

    def broken(builder, clip):
        fn = real(builder, clip)

        def post(leaf4, row_leaf, f, lr):
            gamma, _f_new = fn(leaf4, row_leaf, f, lr)
            return gamma, f
        return post

    monkeypatch.setattr(shared_tree, "_post_fn", broken)
    out = _run(capsys, monkeypatch, "gbm_train")
    assert out["correct"] is False
    over = {k for k, (v, lim) in out["compared"].items() if v > lim}
    assert over & {"leaf_gap", "split_gain_gap", "logloss_gap"}, out


def test_half_of_the_batch_left_out(capsys, monkeypatch):
    """The second half of the rows gets weight 0 in training."""
    import jax.numpy as jnp

    from h2o3_tpu.models.data_info import DataInfo

    real = DataInfo.response_weight

    def halved(y, w_user=None):
        w = real(y, w_user)
        return w * (jnp.arange(w.shape[0]) < w.shape[0] // 2)

    monkeypatch.setattr(DataInfo, "response_weight", staticmethod(halved))
    out = _run(capsys, monkeypatch, "gbm_train")
    assert out["correct"] is False
    assert out["compared"]["cover_gap"][0] > out["compared"]["cover_gap"][1]


def test_an_answer_altered_where_it_is_produced(capsys, monkeypatch):
    """Served probabilities are bent after scoring."""
    from h2o3_tpu import scoring

    real = scoring.score_request

    def bent(model, frame, dest, with_metrics=True):
        pred, mm = real(model, frame, dest, with_metrics=with_metrics)
        col = pred.col("Y")
        col.data = col.data * 0.99
        return pred, mm

    monkeypatch.setattr(scoring, "score_request", bent)
    out = _run(capsys, monkeypatch, "gbm_batch_score")
    assert out["correct"] is False
    assert out["compared"]["pred_gap"][0] > out["compared"]["pred_gap"][1]

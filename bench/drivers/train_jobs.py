"""Whole training jobs back to back through the REST routes."""

from __future__ import annotations

import time

from bench.harness import phases


def setup(run) -> None:
    phases.training_frame(run)
    phases.warm_up_job(run)


def window(run, seconds: float) -> dict:
    t0 = time.perf_counter()
    jobs = []
    while True:
        rec = phases.train_once(run)
        rec["done_at"] = time.perf_counter() - t0
        jobs.append(rec)
        if rec["done_at"] + rec["seconds"] > seconds:
            break
    done = [j for j in jobs if j["status"] == "DONE"]
    span = done[-1]["done_at"] if done else time.perf_counter() - t0
    return {"attempted": len(jobs), "failed": len(jobs) - len(done),
            "jobs": jobs, "jobs_done": len(done), "span_s": span,
            "rows_per_s": run.rows * len(done) / span,
            "row_trees_per_s": run.rows * len(done)
            * int(run.cfg["params"].get("ntrees", 1)) / span}


def collect(run) -> None:
    run.state["produced"] = phases.read_produced(run)
    run.window["iterations"] = run.state["produced"].get("iterations")


def check(run) -> dict:
    if run.window["failed"]:        # a job that never finished gave no answer
        raise RuntimeError(f"{run.window['failed']} job(s) did not end DONE")
    return phases.check_model(run, run.state["produced"])

"""Whole GLM jobs back to back through the REST routes, on a frame of enum
and real columns (the airline recipe), judged by a reference that fits the
same one-hot design.

The window is ``bench/drivers/train_jobs``' own, and the set-up (the frame,
the warm-up job under its deadline and watchdog) is
``bench/drivers/train_jobs_enum``'s. What differs: the program's GLM
counters read over ``GET /3/Metrics`` at the window's start and end, and a
model reader that keeps the deviances a GLM job reports.
"""

from __future__ import annotations

from bench.drivers import train_jobs, train_jobs_enum
from bench.harness import glm_enum, phases

COUNTERS = ("h2o3_glm_iterations_total", "h2o3_glm_gram_passes_total")

setup = train_jobs_enum.setup


def read_counters(rest) -> dict:
    """{counter name: {value of its one label, "" without one: count}} of
    the program's counters this cell reads; a counter the program does not
    have is left out."""
    status, out = rest.request("GET", "/3/Metrics", query={"format": "json"})
    found = {}
    if status != 200 or not out:
        return found
    for series in out.get("series", ()):
        if series.get("name") in COUNTERS:
            found[series["name"]] = {
                next(iter((s.get("labels") or {}).values()), ""):
                float(s["value"]) for s in series.get("samples", ())}
    return found


def window(run, seconds: float) -> dict:
    before = read_counters(run.rest)
    out = train_jobs.window(run, seconds)
    out["counters"] = train_jobs_enum.counters_between(
        before, read_counters(run.rest))
    return out


def collect(run) -> None:
    model_id = run.mix["model_id"]
    produced = glm_enum.read_glm(run.system, model_id)
    doc = run.rest("GET", f"/3/Models/{model_id}")["models"][0]
    reported = doc["output"].get("training_metrics") or {}
    produced["reported"] = {"logloss": reported.get("logloss"),
                            "auc": reported.get("AUC")}
    run.state["produced"] = produced
    run.window["iterations"] = produced["iterations"]


def check(run) -> dict:
    if run.window["failed"]:        # a job that never finished gave no answer
        raise RuntimeError(f"{run.window['failed']} job(s) did not end DONE")
    return phases.check_model(run, run.state["produced"])

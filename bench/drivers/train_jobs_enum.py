"""Whole training jobs back to back through the REST routes, on a frame of
enum and real columns (the airline recipe), judged by a reference that knows
subset splits.

The window is ``bench/drivers/train_jobs``' own. What differs is around it:
the data recipe and the frame (``bench/harness/data_airline``,
``forest_enum``), a warm-up job that must end inside ``WARM_UP_DEADLINE_S``
(a program that cannot run this shape fails the run; it never hangs it),
the program's counters read over ``GET /3/Metrics`` at the window's start
and end, and a forest reader that keeps a categorical split as a set of
levels.
"""

from __future__ import annotations

import os
import sys
import threading
import time

from bench.drivers import train_jobs
from bench.harness import data_airline as recipe
from bench.harness import forest_enum, phases

WARM_UP_DEADLINE_S = 300.0
COUNTERS = ("h2o3_tree_splits_total", "h2o3_forest_walk_total")


def read_counters(rest) -> dict:
    """{counter name: {value of its one label: count}} of the program's
    counters this cell reads; a counter the program does not have is left
    out."""
    status, out = rest.request("GET", "/3/Metrics", query={"format": "json"})
    found = {}
    if status != 200 or not out:
        return found
    for series in out.get("series", ()):
        if series.get("name") in COUNTERS:
            found[series["name"]] = {
                next(iter(s["labels"].values())): float(s["value"])
                for s in series.get("samples", ()) if s.get("labels")}
    return found


def counters_between(before: dict, after: dict) -> dict:
    return {name: {lab: v - before.get(name, {}).get(lab, 0.0)
                   for lab, v in labs.items()}
            for name, labs in after.items()}


def _warm_up_job(run) -> None:
    """One whole job under a deadline of its own: POST, poll; past the
    deadline the job is cancelled and the run ends with an error (a watchdog
    ends the process should a stuck compile keep it alive)."""
    body = phases.job_body(run)
    t0 = time.perf_counter()
    with run.timed("warm_up_job"):
        status, out = run.rest.request(
            "POST", f"/3/ModelBuilders/{run.cfg['algo']}", data=body)
        if status != 200:
            raise RuntimeError(f"warm-up job refused: HTTP {status}: {out}")
        key = out["job"]["key"]["name"]
        while True:
            job = run.rest("GET", f"/3/Jobs/{key}")["jobs"][0]
            if job["status"] in ("DONE", "FAILED", "CANCELLED"):
                break
            if time.perf_counter() - t0 > WARM_UP_DEADLINE_S:
                run.rest.request("POST", f"/3/Jobs/{key}/cancel")
                print(f"bench: warm-up job not DONE in "
                      f"{WARM_UP_DEADLINE_S:.0f} s; cancelled",
                      file=sys.stderr, flush=True)
                timer = threading.Timer(20.0, os._exit, (3,))
                timer.daemon = True
                timer.start()
                raise RuntimeError(
                    f"warm-up job not DONE in {WARM_UP_DEADLINE_S:.0f} s "
                    f"(status {job['status']})")
            time.sleep(0.05)
    if job["status"] != "DONE":
        raise RuntimeError(f"warm-up job ended {job['status']}: "
                           f"{job.get('exception')}")
    run.state["warm_job"] = {"status": "DONE",
                             "model_id": job["dest"]["name"],
                             "seconds": time.perf_counter() - t0}


def setup(run) -> None:
    with run.timed("data"):
        out = recipe.device_columns(run.seed, run.rows,
                                    sharding=run.system.row_sharding())
        run.state["cols"], run.state["y"] = out[:-1], out[-1]
        forest_enum.install_training_frame(
            run.system, phases.TRAIN_KEY, recipe.frame_columns(),
            run.state["cols"], run.state["y"], recipe.RESPONSE_NAME,
            recipe.RESPONSE_DOMAIN)
        run.system.jax.block_until_ready(run.state["y"])
    _warm_up_job(run)


def window(run, seconds: float) -> dict:
    before = read_counters(run.rest)
    out = train_jobs.window(run, seconds)
    out["counters"] = counters_between(before, read_counters(run.rest))
    return out


def collect(run) -> None:
    model_id = run.mix["model_id"]
    produced = forest_enum.read_forest(run.system, model_id)
    doc = run.rest("GET", f"/3/Models/{model_id}")["models"][0]
    produced["reported"] = doc["output"].get("training_metrics") or {}
    run.state["produced"] = produced


def check(run) -> dict:
    if run.window["failed"]:        # a job that never finished gave no answer
        raise RuntimeError(f"{run.window['failed']} job(s) did not end DONE")
    return phases.check_model(run, run.state["produced"])

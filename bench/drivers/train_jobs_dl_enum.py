"""Whole DeepLearning jobs back to back through the REST routes, on a frame
of enum and real columns (the airline recipe), judged by a reference that
trains the same network on the same one-hot design.

The window is ``bench/drivers/train_jobs``' own, and the set-up (the frame,
the warm-up job under its deadline and watchdog) is
``bench/drivers/train_jobs_enum``'s. What differs: the program's DL
counters read over ``GET /3/Metrics`` at the window's start and end, and,
after the window, what the check needs of the program while it still holds
the frame: the trained model's weights, one ``/3/Predictions`` of the
training frame, and a short job of the mix's ``short_job.steps`` steps and
the same seed on a frame of the training frame's first ``short_job.rows``
rows, whose weights the reference replays.
"""

from __future__ import annotations

from bench.drivers import train_jobs, train_jobs_enum
from bench.harness import data_airline as recipe
from bench.harness import dl_enum, forest_enum, phases

COUNTERS = ("h2o3_dl_steps_total", "h2o3_dl_samples_total",
            "h2o3_dl_dispatches_total")
PRED_KEY = "bench_train.pred"

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
    # the jobs' own device peak, before the check's requests add theirs
    out["memory_peak_window_bytes"] = run.system.memory_peak_bytes()
    return out


def _short_job(run) -> dict:
    """One job of ``short_job.steps`` steps on a frame of the training
    frame's first ``short_job.rows`` rows: epochs = steps x batch / rows,
    which the program rounds back to the steps. The frame is small so that
    the steps cross epoch ends: several runs of the training program, the
    key and the optimizer's state carried between them, and a partial last
    epoch. Its seed is the reference's ``clear_seed`` from the window's:
    a trajectory that no rounding can part from the replay at a
    Rectifier's kink."""
    spec = run.mix["short_job"]
    n, steps = int(spec["rows"]), int(spec["steps"])
    sharding = run.system.row_sharding()
    put = run.system.jax.device_put
    cols = tuple(put(c[:n], sharding) for c in run.state["cols"])
    y = put(run.state["y"][:n], sharding)
    forest_enum.install_training_frame(
        run.system, spec["frame"], recipe.frame_columns(), cols, y,
        recipe.RESPONSE_NAME, recipe.RESPONSE_DOMAIN)
    body = phases.job_body(run)
    seed = phases.reference_module(run).clear_seed(cols, y, run.cfg,
                                                   body["seed"], steps)
    body = dict(body, training_frame=spec["frame"], seed=seed,
                model_id=spec["model_id"],
                epochs=steps * int(run.cfg["params"]["mini_batch_size"]) / n)
    rec = run.rest.run_job(run.cfg["algo"], body)
    if rec["status"] != "DONE":
        raise RuntimeError(f"short job ended {rec['status']}: "
                           f"{rec['exception']}")
    return {"seed": seed, "steps": steps, "rows": n,
            "weights": dl_enum.read_dl(run.system,
                                       spec["model_id"])["weights"]}


def collect(run) -> None:
    model_id = run.mix["model_id"]
    produced = dl_enum.read_dl(run.system, model_id)
    doc = run.rest("GET", f"/3/Models/{model_id}")["models"][0]
    reported = doc["output"].get("training_metrics") or {}
    produced["reported"] = {"logloss": reported.get("logloss"),
                            "auc": reported.get("AUC")}
    run.window["posts_after_window"] = 2
    run.rest("POST", f"/3/Predictions/models/{model_id}/frames/"
                     f"{phases.TRAIN_KEY}",
             data={"predictions_frame": PRED_KEY})
    produced["p1"] = run.system.read_column(PRED_KEY,
                                            recipe.RESPONSE_DOMAIN[1])
    produced["short"] = _short_job(run)
    run.state["produced"] = produced


def check(run) -> dict:
    if run.window["failed"]:        # a job that never finished gave no answer
        raise RuntimeError(f"{run.window['failed']} job(s) did not end DONE")
    return phases.check_model(run, run.state["produced"])

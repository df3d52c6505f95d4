"""Whole DeepLearning jobs back to back through the REST routes, on a frame
of 784 int pixel columns and a 10-level response (the MNIST recipe),
judged by a reference that trains the same network on the same dense
design.

The window is ``bench/drivers/train_jobs``' own, and the shape of the rest
is ``bench/drivers/train_jobs_dl_enum``'s: the frame made on the device
from the seed, a warm-up job under a deadline of its own, the program's DL
counters read over ``GET /3/Metrics`` at the window's start and end (with
the units ``max_w2`` rescaled) and what the newest job's ``design`` span
says of its inputs; after the window, for the check only: the trained
model's weights, one ``/3/Predictions`` of the training frame, and a short
job of the mix's ``short_job.steps`` steps with its own parameters (a
``max_w2`` that binds) on a frame of the training frame's first
``short_job.rows`` rows, whose weights the reference replays. With
``--trace 1`` it also reads, while the slice's raw trace is still on disk,
the runs of the programs its two roofline readers measure
(``slice_programs``).
"""

from __future__ import annotations

import os
import time

from bench.drivers import train_jobs, train_jobs_enum
from bench.harness import data_mnist as recipe
from bench.harness import dl_dense, dl_enum, phases, trace_runs

COUNTERS = ("h2o3_dl_steps_total", "h2o3_dl_samples_total",
            "h2o3_dl_dispatches_total", "h2o3_dl_max_w2_rescaled_total")
PRED_KEY = "bench_train.pred"
OP_NAME_CHARS = 200


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


def _install(run, key: str, cols, y) -> None:
    dl_dense.install_training_frame(run.system, key, recipe.NAMES, cols, y,
                                    recipe.RESPONSE_NAME,
                                    recipe.RESPONSE_DOMAIN)


def refuse_unknown_parameters(run) -> None:
    """The configuration's job only means what it says if the program knows
    every parameter of it: ``/3/ModelBuilders/{algo}`` drops an unknown one
    with a warning and trains another network (one without ``max_w2``, with
    the constant columns in its design). So a program that does not know
    one fails the run here, before any data is made."""
    body = dict(phases.job_body(run), training_frame=phases.TRAIN_KEY)
    _status, out = run.rest.request(
        "POST", f"/3/ModelBuilders/{run.cfg['algo']}/parameters", data=body)
    unknown = sorted(m.get("field_name") for m in (out or {}).get(
        "messages", ()) if "unknown parameter" in m.get("message", ""))
    if unknown:
        raise RuntimeError(f"the program does not know the configuration's "
                           f"parameters {unknown}; nothing was run")


def setup(run) -> None:
    refuse_unknown_parameters(run)
    with run.timed("data"):
        out = recipe.device_columns(run.seed, run.rows,
                                    sharding=run.system.row_sharding())
        run.state["cols"], run.state["y"] = out[:-1], out[-1]
        _install(run, phases.TRAIN_KEY, run.state["cols"], run.state["y"])
        run.system.jax.block_until_ready(run.state["cols"])
    train_jobs_enum._warm_up_job(run)


def window(run, seconds: float) -> dict:
    before = read_counters(run.rest)
    out = train_jobs.window(run, seconds)
    out["counters"] = train_jobs_enum.counters_between(
        before, read_counters(run.rest))
    rescaled = out["counters"].get("h2o3_dl_max_w2_rescaled_total")
    if rescaled:
        out["max_w2_rescaled"] = sum(rescaled.values())
    newest = next(iter(run.system.spans("ingress")), [])
    design = dl_dense.design_of(newest)
    for k in ("inputs", "const_cols_dropped"):
        if k in design:
            out[f"design_{k}"] = design[k]
    # the jobs' own device peak, before the check's requests add theirs
    out["memory_peak_window_bytes"] = run.system.memory_peak_bytes()
    return out


def _short_job(run) -> dict:
    """One job of ``short_job.steps`` steps with ``short_job.params`` on a
    frame of the training frame's first ``short_job.rows`` rows: epochs =
    steps x batch / rows, which the program rounds back to the steps (its
    steps cross epoch ends: several runs of the training program, the key
    and the optimizer's state carried between them). Its seed is the
    reference's ``clear_seed`` from the window's."""
    spec = run.mix["short_job"]
    n, steps = int(spec["rows"]), int(spec["steps"])
    over = dict(spec.get("params") or {})
    sharding = run.system.row_sharding()
    put = run.system.jax.device_put
    cols = tuple(put(c[:n], sharding) for c in run.state["cols"])
    y = put(run.state["y"][:n], sharding)
    _install(run, spec["frame"], cols, y)
    body = phases.job_body(run)
    seed = phases.reference_module(run).clear_seed(cols, y, run.cfg,
                                                   body["seed"], steps, over)
    body = dict(body, **over, training_frame=spec["frame"], seed=seed,
                model_id=spec["model_id"],
                epochs=steps * int(run.cfg["params"]["mini_batch_size"]) / n)
    rec = run.rest.run_job(run.cfg["algo"], body)
    if rec["status"] != "DONE":
        raise RuntimeError(f"short job ended {rec['status']}: "
                           f"{rec['exception']}")
    return {"seed": seed, "steps": steps, "rows": n, "over": over,
            "weights": dl_enum.read_dl(run.system,
                                       spec["model_id"])["weights"]}


def reported_metrics(doc: dict) -> dict:
    """The training log loss and classification error a model's REST
    document reports: the error as the confusion matrix's off-diagonal
    share (its table holds one column a predicted class, then Error and
    Rate)."""
    tm = doc["output"].get("training_metrics") or {}
    out = {"logloss": tm.get("logloss"), "err": None}
    table = ((tm.get("cm") or {}).get("table") or {}).get("data")
    if table:
        k = len(table) - 2
        total = sum(sum(col) for col in table[:k])
        right = sum(table[j][j] for j in range(k))
        out["err"] = (total - right) / total if total else None
    return out


def read_slice(run) -> None:
    """The runs in the traced slice of the configuration's ``programs``
    that the cell's roofline readers take, from the raw device trace that
    bench/run.py leaves in its out directory until it reduces it, and the
    seconds the reading took; nothing without a trace."""
    from bench import run as bench_run

    if not getattr(run.args, "trace", 0):
        return
    programs = run.cfg.get("programs", {})
    t0 = time.perf_counter()
    try:
        run.window["slice_programs"] = trace_runs.slice_programs(
            os.path.join(bench_run.OUT_DIR, "trace_" + run.cell["name"]),
            {k: programs[k] for k in ("dl_dense_train", "dl_dense_pass")
             if k in programs})
    except FileNotFoundError:
        return
    run.window["slice_read_s"] = time.perf_counter() - t0


def collect(run) -> None:
    read_slice(run)
    model_id = run.mix["model_id"]
    produced = dl_enum.read_dl(run.system, model_id)
    doc = run.rest("GET", f"/3/Models/{model_id}")["models"][0]
    produced["reported"] = reported_metrics(doc)
    run.window["posts_after_window"] = 2
    run.rest("POST", f"/3/Predictions/models/{model_id}/frames/"
                     f"{phases.TRAIN_KEY}",
             data={"predictions_frame": PRED_KEY})
    produced["probs"] = dl_dense.read_probs(run.system, PRED_KEY,
                                            recipe.RESPONSE_DOMAIN)
    produced["short"] = _short_job(run)
    run.state["produced"] = produced


def trim_op_names(run) -> None:
    """Cut each device operation's name in the reduced trace to its first
    ``OP_NAME_CHARS`` characters. On the chip an operation's name is its
    HLO text, and a loop over this frame's columns carries every column:
    one ``while`` reads about 40 KB, and the ten largest operations would
    make the result line over 100 KB. bench/run.py prints the reduced
    trace's own list after the readers and the check have run, so the cut
    is made here, last, in place."""
    for op in (run.trace or {}).get("device_ops", ()):
        op[0] = op[0][:OP_NAME_CHARS]


def check(run) -> dict:
    trim_op_names(run)
    if run.window["failed"]:        # a job that never finished gave no answer
        raise RuntimeError(f"{run.window['failed']} job(s) did not end DONE")
    return phases.check_model(run, run.state["produced"])

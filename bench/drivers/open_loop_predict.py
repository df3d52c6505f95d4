"""Open-loop /3/Predictions traffic at a fixed rate over one pool of frames.

One dispatcher hands each request to a pool of worker threads at its due
time; every worker keeps one persistent connection. Latency runs from the
due time, so a stall is charged to every request that waited behind it."""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from bench.harness import data as recipe
from bench.harness import phases, stats, traffic
from bench.harness.rest import Rest

DRAIN_S = 60.0


def _drive(run, due, frames, pool):
    """Send request i at t0 + due[i]; -> (t0, sent, done, ok) arrays."""
    n = len(due)
    sent = np.zeros(n)
    done = np.zeros(n)
    ok = np.zeros(n, bool)
    model_id = run.mix["model_id"]
    q = queue.SimpleQueue()

    def worker():
        rest = Rest(run.system.port)
        while True:
            i = q.get()
            if i is None:
                break
            sent[i] = time.perf_counter()
            try:
                with run.annotate("request"):
                    ok[i], _status = phases.predict_once(
                        rest, model_id, pool[frames[i]])
            except Exception:       # noqa: BLE001 — a lost reply is a failure
                ok[i] = False
            done[i] = time.perf_counter()
        rest.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(int(run.mix["workers"]))]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    for i in range(n):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        q.put(i)
    for _ in threads:
        q.put(None)
    deadline = time.perf_counter() + DRAIN_S
    for t in threads:
        t.join(max(deadline - time.perf_counter(), 0.0))
    never = done == 0
    done[never] = time.perf_counter()
    ok[never] = False
    sent[sent == 0] = done[sent == 0]
    return t0, sent, done, ok


def setup(run) -> None:
    pool = phases.serving_setup(run)
    warm_s = float(run.mix.get("warm_seconds", 0))
    if warm_s > 0:                          # the coalesced shapes, at rate
        with run.timed("warm_up_requests"):
            due, frames = traffic.open_loop_schedule(
                run.mix, warm_s, len(pool), run.seed + 1)
            _drive(run, due, frames, pool)


def window(run, seconds: float) -> dict:
    pool = run.state["pools"][run.mix["pool"]]
    due, frames = traffic.open_loop_schedule(run.mix, seconds, len(pool),
                                             run.seed)
    t0, sent, done, ok = _drive(run, due, frames, pool)
    out = stats.open_loop_summary(t0 + due, sent, done, ok, seconds)
    out["latencies_ms"] = stats.latency_table(done, t0 + due, ok,
                                              seconds * 1e3)
    out["rows_scored"] = int(sum(pool[f]["rows"]
                                 for f, g in zip(frames, ok) if g))
    out["span_s"] = float(seconds)
    out["frames_ok"] = sorted({int(f) for f, g in zip(frames, ok) if g})
    return out


def collect(run) -> None:
    """The trained model, and the served answers of a sample of the frames
    the window scored (drawn from the seed, the longest always in it)."""
    run.state["produced"] = phases.read_produced(run)
    pool = run.state["pools"][run.mix["pool"]]
    hit = run.window["frames_ok"]
    k = min(int(run.mix.get("check_sample", len(hit))), len(hit))
    pick = set(recipe.host_rng(run.seed, stream=31)
               .choice(hit, size=k, replace=False).tolist()) if hit else set()
    if hit:
        pick.add(max(hit, key=lambda i: pool[i]["rows"]))
    served = {}
    for i in sorted(pick):
        served[i] = np.asarray(run.system.read_column(pool[i]["dest"],
                                                      recipe.RESPONSE_DOMAIN[1]))
    run.state["served"] = served


def check(run) -> dict:
    numbers = phases.check_model(run, run.state["produced"])
    numbers.update(served_gap(run))
    return numbers


def served_gap(run) -> dict:
    """Widest gap between a served probability and the reference's, over
    the sampled frames (every row of each)."""
    ref = phases.reference_module(run)
    pool = run.state["pools"][run.mix["pool"]]
    served = run.state["served"]
    if not served:
        return {"pred_gap": float("inf"), "pred_rows": 0}
    small = [i for i in served if "X" in pool[i]]
    gap, rows = 0.0, 0
    if small:
        X = np.concatenate([pool[i]["X"] for i in small])
        want = ref.predict(run.state["produced"], run.cfg, X=X)
        got = np.concatenate([served[i] for i in small])
        if got.shape != want.shape:
            return {"pred_gap": float("inf"), "pred_rows": 0}
        gap = max(gap, float(np.max(np.abs(got - want))))
        rows += len(want)
    for i in served:
        if "cols" in pool[i]:
            want = ref.predict(run.state["produced"], run.cfg,
                               cols=pool[i]["cols"])
            if served[i].shape != want.shape:
                return {"pred_gap": float("inf"), "pred_rows": 0}
            gap = max(gap, float(np.max(np.abs(served[i] - want))))
            rows += len(want)
    if not np.isfinite(gap):
        gap = float("inf")
    return {"pred_gap": gap, "pred_rows": rows}

"""Closed-loop /3/Predictions traffic: a few clients, each posting the
frames of one pool in turn and waiting for every reply."""

from __future__ import annotations

import threading
import time

from bench.drivers import open_loop_predict as olp
from bench.harness import phases
from bench.harness.rest import Rest


def setup(run) -> None:
    phases.serving_setup(run)
    warm_s = float(run.mix.get("warm_seconds", 0))
    if warm_s > 0:              # the shapes that concurrent clients coalesce
        with run.timed("warm_up_requests"):
            window(run, warm_s)


def window(run, seconds: float) -> dict:
    pool = run.state["pools"][run.mix["pool"]]
    clients = int(run.mix["clients"])
    model_id = run.mix["model_id"]
    records = []                 # (frame index, done - t0, ok)
    lock = threading.Lock()
    t0 = time.perf_counter()

    def client(c: int):
        rest = Rest(run.system.port)
        i = c * len(pool) // clients
        while time.perf_counter() - t0 < seconds:
            fr = pool[i % len(pool)]
            try:
                with run.annotate("request"):
                    good, _status = phases.predict_once(rest, model_id, fr)
            except Exception:       # noqa: BLE001 — a lost reply is a failure
                good = False
            with lock:
                records.append((i % len(pool),
                                time.perf_counter() - t0, good))
            i += 1
        rest.close()

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # all the work over all the time: no request starts after `seconds`;
    # the ones in flight then are waited for and counted, and the clock
    # runs to the last reply (a count of whole requests inside a fixed
    # window would move in steps of one request)
    good = [r for r in records if r[2]]
    rows = sum(pool[i]["rows"] for i, _d, _g in good)
    span = max((d for _i, d, _g in records), default=float(seconds))
    return {"attempted": len(records),
            "failed": len(records) - len(good),
            "rows_scored": int(rows), "span_s": float(span),
            "rows_per_s": rows / span,
            "frames_ok": sorted({i for i, _d, _g in good})}


collect = olp.collect
check = olp.check

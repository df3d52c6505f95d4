#!/usr/bin/env python3
"""chip_smoke.py — boot, ingest, train, serve once on the chip, through the
entry points users have.

One process (one process may hold a chip). It boots with ``h2o3_tpu.init()``,
starts the REST server in-process with ``h2o3_tpu.start_server()`` and drives
the ``/3/*`` routes over loopback at HIGGS width (28 numeric columns + a
binary enum response, ``BASELINE.json`` config 3) with H2O's default GBM
shape (``nbins=20``, ``max_depth=5``):

  boot   platform / device kind / count / jax version / compile-cache dir
  frame  8,000,000 rows on one chip (16,000,000 on several), from a seed
  ingest a CSV of the same 29 columns through ImportFiles→ParseSetup→Parse
  train  POST /3/ModelBuilders/gbm, poll /3/Jobs; also glm and deeplearning
  serve  POST /3/Predictions/... for 1, 1,000, 16,384 and 100,000 rows,
         each compared with ``model.predict`` on the same frame

Exit code 0 only if every phase passed and the platform is ``tpu``; a phase
that fails ends the run there with the phase named. ``--cpu-dry-run`` runs
the same phases at 20,000 rows on whatever JAX booted (this sandbox, tier-1)
and is the only way the script runs without a chip. The last line of stdout
is one JSON object: ``{"ok": true, "device": {...}}``.

The script sets no compile-cache directory: ``init()`` places JAX's cache
(``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import statistics
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request

SEED = 21
N_FEATURES = 28
ROWS_ONE_CHIP = 8_000_000
ROWS_MULTI_CHIP = 16_000_000
ROWS_DRY_RUN = 20_000
INGEST_ROWS = 200_000
# three row buckets of scoring.py (256 / 1024 / 16384) and the
# chunk-at-top-bucket route
PREDICT_SIZES = (1, 1_000, 16_384, 100_000)
PREDICT_REPEATS = 5
JOB_TIMEOUT_S = 1000.0


@contextlib.contextmanager
def phase(name: str):
    """Name the phase on entry and, when it raises, on the way out — the
    exception itself is never caught, so the run ends there non-zero."""
    print(f"[{name}] ...", flush=True)
    t0 = time.perf_counter()
    try:
        yield
    except BaseException:
        print(f"chip_smoke: FAILED in phase {name!r}", file=sys.stderr,
              flush=True)
        raise
    print(f"[{name}] ok  {time.perf_counter() - t0:.1f}s", flush=True)


class Rest:
    """The few lines of HTTP the smoke needs: JSON in, JSON out, and any
    status other than 200 is an error carrying the server's own body."""

    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"

    def __call__(self, method: str, path: str, data=None, query=None):
        url = self.base + path
        if query:
            url += "?" + urllib.parse.urlencode(query)
        body = json.dumps(data).encode() if data is not None else None
        headers = {"Content-Type": "application/json"} if body else {}
        req = urllib.request.Request(url, data=body, headers=headers,
                                     method=method)
        try:
            with urllib.request.urlopen(req, timeout=JOB_TIMEOUT_S) as resp:
                status, text = resp.status, resp.read().decode()
        except urllib.error.HTTPError as e:
            raise RuntimeError(f"{method} {path} -> HTTP {e.code}: "
                               f"{e.read().decode()[:4000]}") from None
        if status != 200:
            raise RuntimeError(f"{method} {path} -> HTTP {status}")
        return json.loads(text)

    def wait_job(self, job_key: str) -> dict:
        t0 = time.perf_counter()
        while True:
            job = self("GET", f"/3/Jobs/{job_key}")["jobs"][0]
            if job["status"] == "DONE":
                return job
            if job["status"] in ("FAILED", "CANCELLED"):
                raise RuntimeError(f"job {job_key} {job['status']}: "
                                   f"{job.get('exception')}")
            if time.perf_counter() - t0 > JOB_TIMEOUT_S:
                raise TimeoutError(f"job {job_key} still {job['status']} "
                                   f"after {JOB_TIMEOUT_S:.0f}s")
            time.sleep(0.2)


class CompileWatch:
    """What compiling cost, from both books: the repo's compile ledger
    (``obs/compiles.py``: seconds of every ledgered program) and JAX's own
    monitoring events (backend compile seconds of EVERY program, and
    persistent-cache hits and misses — a warm run shows hits and no
    recompile of the tree program)."""

    def __init__(self):
        import jax.monitoring as mon

        self.backend_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> dict:
        return {"t": time.time(), "backend_s": self.backend_s,
                "hits": self.hits, "misses": self.misses}

    def since(self, m: dict) -> dict:
        from h2o3_tpu.obs import compiles

        rows = [r for r in compiles.ledger_rows() if r["ts"] >= m["t"]]
        slowest = max(rows, key=lambda r: r["ms"], default=None)
        return {"ledger_compile_s": round(sum(r["ms"] for r in rows) / 1e3, 2),
                "ledger_programs": len(rows),
                "slowest": (f"{slowest['program']}={slowest['ms'] / 1e3:.2f}s"
                            if slowest else None),
                "backend_compile_s": round(self.backend_s - m["backend_s"], 2),
                "cache_hits": self.hits - m["hits"],
                "cache_misses": self.misses - m["misses"]}


def make_columns(n: int, seed: int):
    """28 standard-normal float32 columns and a binary response drawn from a
    fixed logistic model of them — yields (name, array) one column at a
    time so 8M rows never sit on the host as one matrix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    coef = np.random.default_rng(SEED).uniform(-1.0, 1.0, N_FEATURES)
    logit = np.zeros(n, np.float32)
    for i in range(N_FEATURES):
        x = rng.standard_normal(n, dtype=np.float32)
        logit += np.float32(coef[i]) * x
        yield f"x{i}", x
    y = rng.random(n, dtype=np.float32) < 1.0 / (1.0 + np.exp(-logit))
    yield "y", y.astype(np.int32)


def make_frame(key: str, n: int, seed: int):
    """Resident H2OFrame (installed under `key`) of the generated columns."""
    import h2o3_tpu
    from h2o3_tpu.core.frame import Column

    fr = h2o3_tpu.H2OFrame(destination_frame=key)
    for name, arr in make_columns(n, seed):
        if name == "y":
            fr.add(name, Column.from_numpy(arr, ctype="enum",
                                           domain=["N", "Y"]))
        else:
            fr.add(name, Column.from_numpy(arr))
    return fr


def write_csv(path: str, n: int, seed: int) -> None:
    import numpy as np
    import pandas as pd

    cols = dict(make_columns(n, seed))
    cols["y"] = np.where(cols["y"] == 1, "Y", "N")
    pd.DataFrame(cols).to_csv(path, index=False)


def spans_all_devices(arr, what: str, n_dev: int) -> None:
    got = len(arr.sharding.device_set)
    if got != n_dev:
        raise AssertionError(f"{what} lives on {got} of {n_dev} devices "
                             f"({arr.sharding})")
    print(f"  {what}: on {got}/{n_dev} devices", flush=True)


def finite_auc(rest: Rest, model_id: str, floor: float) -> float:
    out = rest("GET", f"/3/Models/{model_id}")["models"][0]["output"]
    auc = float(out["training_metrics"]["AUC"])
    if not math.isfinite(auc) or auc <= floor:
        raise AssertionError(f"{model_id}: training AUC {auc} (need finite "
                             f"and > {floor})")
    return auc


def train(rest: Rest, watch: CompileWatch, algo: str, frame_key: str,
          floor: float, **params) -> dict:
    mark = watch.mark()
    t0 = time.perf_counter()
    out = rest("POST", f"/3/ModelBuilders/{algo}",
               data={"training_frame": frame_key, "response_column": "y",
                     **params})
    job = rest.wait_job(out["job"]["key"]["name"])
    job_s = time.perf_counter() - t0
    model_id = job["dest"]["name"]
    auc = finite_auc(rest, model_id, floor)
    rec = {"model": model_id, "auc": round(auc, 5), "job_s": round(job_s, 2),
           **watch.since(mark)}
    print(f"  {algo}: HTTP 200, job DONE in {job_s:.2f}s, AUC {auc:.5f}, "
          f"compile inside the job: ledger {rec['ledger_compile_s']}s over "
          f"{rec['ledger_programs']} programs (slowest {rec['slowest']}), "
          f"jax backend {rec['backend_compile_s']}s, persistent cache "
          f"{rec['cache_hits']} hits / {rec['cache_misses']} misses",
          flush=True)
    return rec


def serve(rest: Rest, model_id: str, n: int):
    """POST /3/Predictions for an n-row frame: 200, finite, and equal to
    model.predict on the same frame to 1e-6. Returns the record and the
    served predictions frame."""
    import numpy as np

    from h2o3_tpu.core.dkv import DKV
    from h2o3_tpu.memory import budget, stream

    key = f"smoke_score_{n}.hex"
    frame = make_frame(key, n, seed=SEED + n)
    path = f"/3/Predictions/models/{model_id}/frames/{key}"
    c0 = stream.counters()
    planned = budget.plan("scoring", n)
    t0 = time.perf_counter()
    out = rest("POST", path, data={})
    first_s = time.perf_counter() - t0
    c1 = stream.counters()
    if c1["pressure_failures"] != c0["pressure_failures"]:
        raise AssertionError(f"{n} rows: the memory planner refused")
    mode = "chunked" if c1["chunked_runs"] > c0["chunked_runs"] else "full"
    lat = []
    for _ in range(PREDICT_REPEATS):
        t0 = time.perf_counter()
        out = rest("POST", path, data={})
        lat.append((time.perf_counter() - t0) * 1e3)
    served = DKV.get(out["predictions_frame"]["name"])
    direct = DKV.get(model_id).predict(frame)
    if served.nrows != n or served.names != direct.names:
        raise AssertionError(f"{n} rows: served {served.nrows} x "
                             f"{served.names}, direct {direct.names}")
    worst = 0.0
    for name in served.names:
        a = np.asarray(served.col(name).data)[:n].astype(np.float64)
        b = np.asarray(direct.col(name).data)[:n].astype(np.float64)
        if not np.all(np.isfinite(a)):
            raise AssertionError(f"{n} rows: column {name} is not finite")
        worst = max(worst, float(np.max(np.abs(a - b))))
    if worst > 1e-6:
        raise AssertionError(f"{n} rows: REST and model.predict differ by "
                             f"{worst:g}")
    p50 = statistics.median(lat)
    print(f"  {n:>7} rows: HTTP 200, finite, max|REST - model.predict| = "
          f"{worst:.1e}; plan {mode} (planner: {planned.mode}, "
          f"{planned.row_bytes:.0f} B/row, {c1['windows'] - c0['windows']} "
          f"windows a request); first {first_s:.2f}s, "
          f"p50 of {PREDICT_REPEATS} more {p50:.1f} ms", flush=True)
    rec = {"rows": n, "plan": mode, "first_s": round(first_s, 3),
           "p50_ms": round(p50, 2), "max_abs_diff": worst}
    return rec, served


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help=f"same phases at {ROWS_DRY_RUN:,} rows on whatever "
                         "JAX booted; the only way to run without a chip")
    ap.add_argument("--rows", type=int, default=None,
                    help="training rows (default 8,000,000 on one chip, "
                         "16,000,000 on several); cut rows only, never the "
                         "28 columns")
    args = ap.parse_args()
    t_start = time.perf_counter()

    with phase("boot"):
        import jax

        import h2o3_tpu

        cl = h2o3_tpu.init()
        dev = jax.devices()[0]
        n_dev = len(jax.devices())
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": n_dev}
        print(f"  platform={dev.platform}"
              f"{' DRY RUN' if args.cpu_dry_run else ''} "
              f"device_kind={dev.device_kind!r} devices={n_dev} "
              f"mesh={dict(cl.mesh.shape)} jax={jax.__version__} "
              f"compile_cache={jax.config.jax_compilation_cache_dir}",
              flush=True)
        if dev.platform != "tpu" and not args.cpu_dry_run:
            raise SystemExit(
                f"chip_smoke: JAX booted platform {dev.platform!r}, not a "
                f"TPU. Nothing was run. (--cpu-dry-run runs the phases at "
                f"{ROWS_DRY_RUN:,} rows without a chip.)")
        watch = CompileWatch()
        stats = dev.memory_stats() or {}
        print(f"  device bytes_limit={stats.get('bytes_limit')}", flush=True)
        srv = h2o3_tpu.start_server(port=0)
        rest = Rest(srv.port)
        print(f"  REST server on {rest.base}: "
              f"{rest('GET', '/3/Cloud')['cloud_size']} device(s) in the "
              f"cloud", flush=True)

    n_rows = args.rows or (ROWS_DRY_RUN if args.cpu_dry_run
                           else ROWS_ONE_CHIP if n_dev == 1
                           else ROWS_MULTI_CHIP)
    result = {"rows": n_rows, "cols": N_FEATURES + 1}
    try:
        with phase("frame"):
            train_fr = make_frame("smoke_train.hex", n_rows, seed=SEED)
            jax.block_until_ready([c.data for c in train_fr.columns])
            print(f"  smoke_train.hex: {train_fr.nrows:,} x {train_fr.ncols}"
                  f", {sum(c.device_nbytes for c in train_fr.columns) / 1e9:.2f}"
                  f" GB resident", flush=True)
            spans_all_devices(train_fr.col("x0").data, "frame column x0",
                              n_dev)
            in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                      for d in jax.devices()]
            print(f"  per-device bytes_in_use: {in_use}", flush=True)
            result["bytes_in_use"] = in_use

        with phase("ingest"):
            from h2o3_tpu.ingest import chunked
            from h2o3_tpu.native import loader

            n_csv = min(INGEST_ROWS, n_rows)
            tmp = tempfile.mkdtemp(prefix="chip_smoke_")
            csv = os.path.join(tmp, "smoke_ingest.csv")
            write_csv(csv, n_csv, seed=SEED + 1)
            c0 = chunked.counters()
            t0 = time.perf_counter()
            files = rest("GET", "/3/ImportFiles", query={"path": csv})
            setup = rest("POST", "/3/ParseSetup",
                         data={"source_frames": files["files"]})
            parse = rest("POST", "/3/Parse",
                         data={"source_frames": files["files"],
                               "destination_frame": "smoke_ingest.hex"})
            rest.wait_job(parse["job"]["key"]["name"])
            parse_s = time.perf_counter() - t0
            c1 = chunked.counters()
            info = rest("GET", "/3/Frames/smoke_ingest.hex")["frames"][0]
            if (int(info["rows"]), int(info["num_columns"])) != \
                    (n_csv, N_FEATURES + 1):
                raise AssertionError(f"parsed {info['rows']} x "
                                     f"{info['num_columns']}")
            if setup["column_types"][-1].lower() != "enum":
                raise AssertionError(f"response parsed as "
                                     f"{setup['column_types'][-1]}")
            # which parser ran: rows that came in through the chunked
            # byte-range path (pandas C engine per chunk), else the
            # monolithic path (native .so for all-numeric files, pandas
            # otherwise). The .so is rebuilt when its sources' hash moved.
            lib = loader.get_lib()
            parser = ("chunked" if c1["chunk_rows"] - c0["chunk_rows"] == n_csv
                      else "monolithic")
            print(f"  {n_csv:,}-row CSV ({os.path.getsize(csv) / 1e6:.0f} MB)"
                  f" -> smoke_ingest.hex in {parse_s:.2f}s; parser={parser} "
                  f"({c1['chunks'] - c0['chunks']} chunks); native .so "
                  f"{'built from these sources' if lib else 'unavailable'}",
                  flush=True)
            result["parser"] = parser
            os.remove(csv)
            os.rmdir(tmp)

        with phase("train gbm"):
            gbm = train(rest, watch, "gbm", "smoke_train.hex", 0.7,
                        ntrees=10, max_depth=5, nbins=20, seed=SEED)
            result["gbm"] = gbm
            from h2o3_tpu.core.dkv import DKV

            binned = DKV.get(gbm["model"]).spec.bin_columns(train_fr)
            print(f"  binned matrix {binned.shape} {binned.dtype}",
                  flush=True)
            spans_all_devices(binned, "binned matrix", n_dev)
            del binned

        with phase("serve"):
            result["serve"] = []
            for n in PREDICT_SIZES:
                rec, served = serve(rest, gbm["model"], n)
                result["serve"].append(rec)
            spans_all_devices(served.col(served.names[-1]).data,
                              f"prediction column of {n} rows", n_dev)
            from h2o3_tpu.memory import budget

            snap = budget.snapshot()
            rebin = budget.plan("binning", n_rows)
            print(f"  planner: budget_bytes={snap['budget_bytes']} "
                  f"free_bytes={snap['free_bytes']} "
                  f"live_bytes={snap['live_bytes']} "
                  f"row_bytes={snap['row_bytes_estimates']}; binning the "
                  f"training frame again would now be planned {rebin.mode} "
                  f"(chunk {rebin.chunk_rows:,} rows)", flush=True)
            result["planner"] = {**snap["row_bytes_estimates"],
                                 "rebin_plan": rebin.mode}

        with phase("train glm"):
            result["glm"] = train(rest, watch, "glm", "smoke_train.hex", 0.5,
                                  **{"family": "binomial", "lambda": 0.0,
                                     "seed": SEED})

        with phase("train deeplearning"):
            result["deeplearning"] = train(
                rest, watch, "deeplearning", "smoke_train.hex", 0.5,
                hidden="[200,200]", epochs=1, seed=SEED)
    finally:
        srv.stop()

    result["wall_s"] = round(time.perf_counter() - t_start, 1)
    print("chip_smoke result " + json.dumps({"device": device, **result}),
          flush=True)
    final = {"ok": True, "device": device}
    if args.cpu_dry_run:
        final["dry_run"] = True
    print(json.dumps(final), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

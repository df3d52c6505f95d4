#!/usr/bin/env python3
"""bench/run.py — one run of one cell of BENCHMARK.json.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. It boots the system the way a user does (``h2o3_tpu.init()``,
``h2o3_tpu.start_server(port=0)``), makes its data from ``--seed``, warms up
every shape the cell's traffic uses (all of that is ``setup_s``), drives the
``/3/*`` routes over loopback for ``--seconds``, reads the device's peak
memory, frees the program's state and lets the plain reference decide
``correct``. The last line of stdout is one JSON object.

Everything that belongs to one cell is data found by name:
  BENCHMARK.json workloads[] -> bench/configs/<config>.json
                             -> bench/mixes/<traffic>.json -> its "driver"
                             -> bench/drivers/<driver>.py
  per_layer[].name           -> bench/layer_metrics/<stem of the name>.py
  config "reference"         -> bench/reference/<name>.py
  config "roofline"          -> bench/roofline/<program>.py
Nothing here branches on a cell's or a configuration's name.

It refuses to run unless JAX booted a TPU with exactly the chips the cell
asks for. ``--cpu-dry-run`` rehearses the same phases at the configuration's
``dry_run_rows`` on whatever JAX booted and says so (``platform=cpu DRY
RUN``); a dry run's numbers are never device numbers.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse      # noqa: E402
import contextlib    # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import traceback     # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
OUT_DIR = os.path.join(ROOT, ".bench_out")


def place_caches(mix: dict = None, cfg: dict = None) -> None:
    """Whatever the program or JAX caches goes inside the checkout, at fixed
    paths (the path is part of a compile cache's key). The program places
    JAX's persistent cache itself (``<checkout>/.jax_cache`` unless
    JAX_COMPILATION_CACHE_DIR is set); here only its scratch root is moved
    off /tmp, and JAX is told to keep small programs too, so that a second
    run in a checkout compiles nothing again. A configuration or a mix may
    state its own environment (``"env"`` in its file, e.g. the matmul
    precision a configuration states); that wins."""
    for src in (cfg, mix):
        for k, v in ((src or {}).get("env") or {}).items():
            os.environ[k] = str(v)
    os.environ.setdefault("H2O_TPU_ICE_ROOT", os.path.join(OUT_DIR, "ice"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")


def find_cell(manifest: dict, name: str):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                         f"({sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "mixes",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return cell, cfg, mix


def metrics_of(manifest: dict, section: str, cell: str, reported: set):
    """The manifest's metrics of one section that this cell reports."""
    out = []
    for m in manifest[section]:
        cells = m.get("workloads")
        if cells is not None and cell not in cells:
            continue
        if cells is None and section == "per_layer" \
                and m["moves"] not in reported:
            continue
        out.append(m)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="manifest to read, relative to the checkout "
                         "(tests rehearse cells that are not admitted yet)")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="rehearse at dry_run_rows on whatever JAX booted")
    args = ap.parse_args()

    with open(os.path.join(ROOT, args.manifest)) as f:
        manifest = json.load(f)
    cell, cfg, mix = find_cell(manifest, args.workload)
    place_caches(mix, cfg)

    from bench.harness import phases, trace as tracelib
    from bench.harness.compilewatch import CompileWatch
    from bench.harness.system import System
    from bench.roofline import peaks

    system = System(chips=int(cell["chips"]), dry_run=args.cpu_dry_run)
    dev = system.device
    print(f"bench: platform={dev['platform']}"
          f"{' DRY RUN' if args.cpu_dry_run else ''} "
          f"device_kind={dev['kind']!r} devices={dev['count']} "
          f"workload={cell['name']} seed={args.seed}", file=sys.stderr,
          flush=True)
    watch = CompileWatch()
    annotate = tracelib.annotate if args.trace else \
        (lambda name: contextlib.nullcontext())
    run = phases.Run(args, cell, cfg, mix, system, watch, annotate)
    run.setup_parts["boot"] = system.boot_s
    if dev["platform"] == "tpu":
        run.peak = peaks.peak_for(dev["kind"])     # unknown kind: an error
    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")

    rc = 1
    try:
        # ---- set-up: data, compile or cache load, warm-up -------------------
        setup_mark = watch.mark()
        driver.setup(run)
        setup_compiles = watch.since(setup_mark)
        setup_s = time.time() - T_PROCESS

        # ---- the measured window ---------------------------------------------
        tracer = None
        if args.trace:
            spec = mix.get("trace", {})
            tracer = tracelib.SliceTracer(
                os.path.join(OUT_DIR, "trace_" + cell["name"]),
                spec.get("start_s", 0.0), spec.get("seconds"))
            tracer.arm()
        win_mark = watch.mark()
        run.window = driver.window(run, float(args.seconds))
        in_window = watch.since(win_mark)
        # real compiles: requests that the persistent cache did not serve
        run.window_compiles = in_window["compiles"] - in_window["hits"]
        traced_s = tracer.stop() if tracer else 0.0
        driver.collect(run)
        device = dict(dev, memory_peak_bytes=system.memory_peak_bytes())

        # ---- metrics -------------------------------------------------------------
        quantities = dict(run.window, setup_s=setup_s)
        reports = dict(mix["reports"], setup_s="setup_s")
        metrics = {}
        breakdown = None
        if not args.trace:
            for m in metrics_of(manifest, "end_to_end", cell["name"], set()):
                metrics[m["name"]] = {
                    "value": float(quantities[reports[m["name"]]]),
                    "unit": m["unit"]}
        else:
            if traced_s > 0:
                raw = tracelib.load_xplane(tracer.out_dir)
                run.trace = tracelib.reduce(raw, window_s=traced_s)
                shutil.rmtree(tracer.out_dir, ignore_errors=True)
                device["busy_s"] = run.trace["busy_s"]
                device["window_s"] = run.trace["window_s"]
                breakdown = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
            for m in metrics_of(manifest, "per_layer", cell["name"],
                                set(reports)):
                stem = m["name"].split(".")[0]
                reader = importlib.import_module(
                    f"bench.layer_metrics.{stem}")
                value = reader.read(run, m["name"])
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}

        # ---- correct: the plain reference, with the device to itself ------------
        system.free_program_state()
        t_check = time.perf_counter()
        try:
            numbers = driver.check(run)
        except Exception:           # noqa: BLE001 — a check that dies is a fail
            traceback.print_exc()
            numbers = {}
        check_s = time.perf_counter() - t_check
        limits = cfg.get("limits", {})
        compared = phases.compare(numbers, limits)
        missing = [k for k in limits if k not in numbers
                   and k not in cfg.get("limits_optional", [])]
        correct = bool(compared) and not missing and all(
            v == v and v <= lim for v, lim in compared.values()) \
            and run.window["attempted"] > 0

        result = {"correct": correct,
                  "attempted": int(run.window["attempted"]),
                  "failed": int(run.window["failed"]),
                  "metrics": metrics, "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["info"] = {
            "dry_run": bool(args.cpu_dry_run), "rows": run.rows,
            "setup_parts_s": run.setup_parts,
            "setup_compiles": setup_compiles,
            "window_compiles": run.window_compiles,
            "check_s": check_s,
            "window": {k: v for k, v in run.window.items()
                       if isinstance(v, (int, float, str))},
            "jobs": [{k: j.get(k) for k in ("status", "seconds",
                                            "builder_ms")}
                     for j in run.window.get("jobs", [])],
            "numbers": {k: float(v) for k, v in numbers.items()},
            "missing": missing,
        }
        result["compared"] = compared
        for k, (v, lim) in compared.items():
            print(f"compared {k} {v!r} limit {lim!r} "
                  f"{'ok' if v == v and v <= lim else 'OVER'}",
                  file=sys.stderr)
        for k in missing:
            print(f"compared {k} missing", file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
        rc = 0
    finally:
        system.stop()
    return rc


if __name__ == "__main__":
    sys.exit(main())

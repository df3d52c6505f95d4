#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip, in one process.

    python3 bench/tests/sweep_on_chip.py --workload gbm_lookup --seed 7 \
        --rates 20 40 80 160 320 --step-seconds 8

The cell's own set-up, then one step per rate with the cell's own driver and
mix (only ``rate_rps`` is replaced). The knee is the highest step that
completes what it was offered with no growing backlog (the last quarter of
its requests no slower than twice the first quarter). One JSON line a step.
The mix file then gets four fifths of the knee. Not run by the benchmark.
"""

import argparse
import contextlib
import importlib
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--step-seconds", type=float, default=8.0)
    ap.add_argument("--passes", type=int, default=1)
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--cpu-dry-run", action="store_true")
    args = ap.parse_args()
    from bench import run as bench_run

    with open(os.path.join(ROOT, args.manifest)) as f:
        manifest = json.load(f)
    cell, cfg, mix = bench_run.find_cell(manifest, args.workload)
    bench_run.place_caches(mix, cfg)
    from bench.harness import phases
    from bench.harness.compilewatch import CompileWatch
    from bench.harness.system import System

    system = System(chips=int(cell["chips"]), dry_run=args.cpu_dry_run)
    watch = CompileWatch()
    run = phases.Run(args, cell, cfg, mix, system, watch,
                     lambda name: contextlib.nullcontext())
    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    try:
        driver.setup(run)
        for p in range(args.passes):
            for k, rate in enumerate(args.rates):
                run.mix = dict(mix, rate_rps=rate)
                run.seed = args.seed + 1000 * p + k
                mark = watch.mark()
                w = driver.window(run, args.step_seconds)
                lat = w.pop("latencies_ms", [])
                compiled = watch.since(mark)
                q = max(len(lat) // 4, 1)
                first, last = lat[:q], lat[-q:]
                out = {"pass": p, "rate_rps": rate, "seed": run.seed,
                       "attempted": w["attempted"], "failed": w["failed"],
                       "completed_per_s": w["completed_per_s"],
                       "p50_ms": w["p50_ms"], "p95_ms": w["p95_ms"],
                       "late_p99_ms": w["late_p99_ms"],
                       "first_quarter_p50_ms": statistics.median(first)
                       if first else None,
                       "last_quarter_p50_ms": statistics.median(last)
                       if last else None,
                       "compiles": compiled["compiles"] - compiled["hits"],
                       "compile_s": compiled["backend_s"],
                       "platform": system.device["platform"]}
                print(json.dumps(out), flush=True)
    finally:
        system.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Read the control and the faults at a cell's own size, on the chip.

    python3 bench/tests/control_on_chip.py --config higgs_gbm_d5 --seeds 11 12 13

No program is involved: data from the benchmark's recipe, the reference put in
the program's place (bench/reference/<name>.controls) at the lower precision
and with each fault planted, judged by the same reference that judges a run.
One JSON line per (seed, label) on stdout. The benchmark's own runs never run
this; bench/tests/test_control.py holds the same at a size a test can hold.
"""

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--which", nargs="*", default=None)
    ap.add_argument("--cpu-dry-run", action="store_true")
    args = ap.parse_args()
    import jax

    from bench.harness import data as recipe

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_dry_run:
        raise SystemExit(f"platform {dev.platform!r} is not a TPU")
    with open(os.path.join(ROOT, "bench", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    rows = args.rows or (cfg["dry_run_rows"] if args.cpu_dry_run
                         else cfg["rows"])
    ref = importlib.import_module(f"bench.reference.{cfg['reference']}")
    kw = {"which": tuple(args.which)} if args.which else {}
    for seed in args.seeds:
        out = recipe.device_columns(seed, rows)
        cols, y = out[:-1], out[-1]
        t0 = time.perf_counter()
        for label, numbers in ref.controls(cols, y, cfg, **kw):
            print(json.dumps({"config": args.config, "rows": rows,
                              "platform": dev.platform, "seed": seed,
                              "label": label,
                              "seconds": time.perf_counter() - t0,
                              "numbers": numbers}), flush=True)
            t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())

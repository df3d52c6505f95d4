#!/usr/bin/env python3
"""Read the control and the planted faults of the enum cell at its own size,
on the chip (beside bench/tests/control_on_chip.py, which holds the HIGGS
recipe).

    python3 bench/tests/control_enum_on_chip.py --config airline_gbm_d10 \
        --seeds 11 --which control code_order
    python3 bench/tests/control_enum_on_chip.py --seeds 11 \
        --na-share 0.00390625 --which na_flipped

The second line is the recipe's variant with missing rows: the cell's frame
has none, as the source's file has none, so the fault ``na_flipped`` can
only be seen there.

No program is involved: data from the airline recipe, the reference put in
the program's place (bench/reference/gbm_enum.controls) at the lower
precision and with each fault planted, judged by the same reference that
judges a run. One JSON line per (seed, label) on stdout, each number beside
its limit. The benchmark's own runs never run this;
tests/test_airline_gbm_reference.py holds the same at a size a test can hold.
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
    ap.add_argument("--config", default="airline_gbm_d10")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--which", nargs="*", default=None)
    ap.add_argument("--na-share", type=float, default=None,
                    help="share of missing rows in the recipe's variant")
    ap.add_argument("--cpu-dry-run", action="store_true")
    args = ap.parse_args()
    import jax

    from bench.harness import data_airline as recipe

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
        out = recipe.device_columns(seed, rows, na_share=args.na_share)
        cols, y = out[:-1], out[-1]
        t0 = time.perf_counter()
        for label, numbers in ref.controls(cols, y, cfg, **kw):
            over = sorted(k for k, lim in cfg["limits"].items()
                          if not numbers[k] <= lim)
            print(json.dumps({"config": args.config, "rows": rows,
                              "platform": dev.platform, "seed": seed,
                              "na_share": args.na_share or 0.0,
                              "label": label, "fails": over,
                              "seconds": time.perf_counter() - t0,
                              "numbers": numbers}), flush=True)
            t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Read the control and the planted faults of the DeepLearning cell on an
all-numeric frame (mnist_dl_train) at its own size, on the chip.

    python3 bench/tests/control_dl_dense_on_chip.py --seeds 11

No program is involved: data from the MNIST recipe, the reference put in
the program's place (bench/reference/dl_dense.controls) at the lower
precision and with each fault planted (max_w2 skipped, constant columns
kept, L1 dropped, hidden dropout off), a replay of the mix's short job on
the frame's first rows, with its own parameters, from the job seed the
harness would post (``clear_seed``'s), judged by the same reference that
judges a run. One JSON line per (seed, label) on stdout, each number beside
its limit. The benchmark's own runs never run this;
tests/test_mnist_dl_reference.py holds the same at a size a test can hold.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="mnist8m_dl_1024x1024x2048")
    ap.add_argument("--mix", default="train_jobs_dl_dense")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--which", nargs="*", default=None)
    ap.add_argument("--cpu-dry-run", action="store_true")
    args = ap.parse_args()
    import jax

    from bench.harness import data_mnist as recipe
    from bench.reference import dl_dense

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_dry_run:
        raise SystemExit(f"platform {dev.platform!r} is not a TPU")
    with open(os.path.join(ROOT, "bench", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "bench", "mixes", args.mix + ".json")) as f:
        short = json.load(f)["short_job"]
    rows = args.rows or (cfg["dry_run_rows"] if args.cpu_dry_run
                         else cfg["rows"])
    kw = {"which": tuple(args.which)} if args.which else {}
    n, steps = int(short["rows"]), int(short["steps"])
    over = dict(short.get("params") or {})
    for seed in args.seeds:
        out = recipe.device_columns(seed, rows)
        t0 = time.perf_counter()
        job_seed = dl_dense.clear_seed([c[:n] for c in out[:-1]],
                                       out[-1][:n], cfg,
                                       recipe.fold_seed(seed)[0], steps,
                                       over)
        for label, numbers in dl_dense.controls(out[:-1], out[-1], cfg,
                                                job_seed, steps, n, over,
                                                **kw):
            over_limit = sorted(k for k, lim in cfg["limits"].items()
                                if not numbers[k] <= lim)
            print(json.dumps({"config": args.config, "rows": rows,
                              "platform": dev.platform, "seed": seed,
                              "job_seed": job_seed, "label": label,
                              "fails": over_limit,
                              "seconds": time.perf_counter() - t0,
                              "numbers": numbers}), flush=True)
            t0 = time.perf_counter()
        del out
    return 0


if __name__ == "__main__":
    sys.exit(main())

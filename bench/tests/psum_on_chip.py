#!/usr/bin/env python3
"""What the collectives of a sharded cell cost, a device and a level. By
hand, on the chip, in one process:

    python3 bench/tests/psum_on_chip.py --workload airline_gbm_train_4chip \
        --seed 7 --seconds 40

This is ``bench/run.py --trace 1`` of the cell (its last line is that run's
result line, per-layer metrics and all) with one thing added: before the
harness reduces the capture to averages over the devices and deletes it,
the ``.xplane.pb`` is read the way ``gaps_on_chip.py`` reads it (the
protobuf, so that an operation's ``tf_op`` scope is seen) and kept apart by
device. For every device plane and every compiled program that ran a
collective (``all-reduce*`` and its kin):

  - the program's runs and seconds on that device,
  - the seconds of its collective operations: those named as one
    (``all-reduce*`` ...; a trace names an operation by its HLO text,
    ``%all-reduce.5 = ...``, or by a short display name) and whatever else
    carries a ``psum`` scope, e.g. a fusion the compiler made of the
    all-reduce (own time: a ``-start`` and its ``-done`` both count; on the
    device that reaches an all-reduce first the wait for the slowest shard
    is inside it),
  - those seconds by site: the ``psum`` scope the program put around the
    call (``level<d>/hist/psum``, ``leaf_sums/psum``, ``stats/psum``), or
    ``#k``, the operation's place among the collectives of its run, where the
    compiled program came from a cache that holds no scopes.

Written to ``chiprun_out/psum_<cell>_<seed>.json`` and as a table on stderr.
``--xplane <file>`` reads a capture again, with no chip. Not run by the
benchmark.
"""

import argparse
import bisect
import collections
import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench.tests import gaps_on_chip as gaps  # noqa: E402

COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")
SITE = re.compile(r"(?:^|/)((?:level\d+/hist|leaf_sums|stats)/psum)(?:/|$)")


def by_device(devices: list) -> list:
    """One record a device plane, in the capture's order: {"busy_s",
    "programs": {name: {"runs", "seconds", "collective_s", "sites": {site:
    seconds}, "ops": {kind of operation: events}}}} for the programs that
    ran a collective."""
    from bench.harness import trace as tracelib

    out = []
    for dev in devices:
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        progs = {}
        nth = collections.Counter()          # (program run start) -> count
        ops = dev["ops"]
        own = gaps.own_ns(ops)
        for i in sorted(range(len(ops)), key=lambda i: ops[i][1]):
            name, s, _d, stats = ops[i]
            m = SITE.search(stats.get("tf_op", ""))
            named = COLLECTIVE.match(name)
            if not (m or named):
                continue
            j = bisect.bisect_right(starts, s) - 1
            prog = gaps.module_at(modules, starts, s)
            kind = name.lstrip("%").split(" ")[0].rstrip("0123456789.")
            if m:
                site = m.group(1)
            else:           # a -start and its -done share a place
                if not kind.endswith("-done"):
                    nth[j] += 1
                site = f"#{nth[j]}"
            rec = progs.setdefault(prog, {"collective_s": 0.0, "sites": {},
                                          "ops": {}})
            rec["collective_s"] += own[i] / 1e9
            rec["sites"][site] = rec["sites"].get(site, 0.0) + own[i] / 1e9
            rec["ops"][kind] = rec["ops"].get(kind, 0) + 1
        for name, _s, d in modules:
            prog = re.sub(r"\(\d+\)$", "", name)
            if prog in progs:
                progs[prog]["runs"] = progs[prog].get("runs", 0) + 1
                progs[prog]["seconds"] = progs[prog].get("seconds", 0.0) \
                    + d / 1e9
        merged = tracelib._union([(s, s + d) for _n, s, d, _st in ops])
        out.append({"busy_s": sum(e - s for s, e in merged) / 1e9,
                    "programs": progs})
    return out


def report(result: dict, keep: bool = True) -> None:
    err = sys.stderr
    for k, dev in enumerate(result["devices"]):
        print(f"\ndevice {k}: busy {dev['busy_s']:.3f} s", file=err)
        for prog, rec in sorted(dev["programs"].items(),
                                key=lambda kv: -kv[1]["collective_s"]):
            secs = rec.get("seconds", 0.0)
            share = 100.0 * rec["collective_s"] / secs if secs else 0.0
            print(f"  {prog}: {rec.get('runs', 0)} runs, {secs:.4f} s, "
                  f"collectives {rec['collective_s']:.4f} s ({share:.2f}%)",
                  file=err)
            for site, v in sorted(rec["sites"].items()):
                print(f"    {v:10.5f}  {site}", file=err)
    if keep:
        os.makedirs(gaps.KEEP_DIR, exist_ok=True)
        path = os.path.join(gaps.KEEP_DIR, "psum_%s_%s.json" % (
            result["workload"], result["seed"]))
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
    err.flush()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window; default: BENCHMARK.json's run_seconds")
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--cpu-dry-run", action="store_true")
    ap.add_argument("--xplane", default=None,
                    help="read this .xplane.pb again and run nothing")
    args = ap.parse_args()
    if args.xplane:
        report({"workload": args.workload, "seed": args.seed,
                "devices": by_device(gaps.load(args.xplane)[0])},
               keep=False)
        return 0
    from bench import run as bench_run
    from bench.harness import trace as tracelib

    with open(os.path.join(ROOT, args.manifest)) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    reduce_capture = tracelib.load_xplane

    def read_first(trace_dir: str) -> dict:
        paths = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if paths:
            report({"workload": args.workload, "seed": args.seed,
                    "xplane_bytes": os.path.getsize(paths[-1]),
                    "devices": by_device(gaps.load(paths[-1])[0])})
        return reduce_capture(trace_dir)

    tracelib.load_xplane = read_first
    sys.argv = [bench_run.__file__, "--workload", args.workload, "--seed",
                str(args.seed), "--seconds", str(seconds), "--trace", "1",
                "--manifest", args.manifest] \
        + (["--cpu-dry-run"] if args.cpu_dry_run else [])
    return bench_run.main()


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""What the device's idle time and busy time are made of, by the program's
own names. By hand, on the chip, in one process:

    python3 bench/tests/gaps_on_chip.py --workload gbm_batch_score --seed 7

The cell's own set-up and window under the harness's ``SliceTracer`` (the
slice the cell's mix states), with the ``.xplane.pb`` kept until it is read.
Two tables:

  (i)  idle seconds by program span: every gap between device operations,
       named by the ``h2o3.<span>`` host event (``obs/tracing`` mirrors its
       live spans into the capture) that covers most of it, the most
       specific one on a tie; ``host_other`` where none does;
  (ii) device seconds by scope: each operation's own time (what its nested
       operations take is theirs: a ``while`` holds its body), summed by the
       ``jax.named_scope`` in the operation's ``tf_op`` (a stat of the
       event's metadata, which only the protobuf shows) and by the compiled
       program the operation ran in.

One JSON line at the end, also written to ``chiprun_out/gaps_<cell>.json``
with the stats of a few operations as the trace holds them (to see by hand
which stat carries the scope). A trace of at most 40 MB comes back beside it
and can be read again here, with no chip: ``--xplane <file>``. Not run by
the benchmark.
"""

import argparse
import bisect
import collections
import glob
import importlib
import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

SCOPE = re.compile(r"(?:^|/)(level\d+/(?:hist|search|route)|leaf_sums|bin|"
                   r"walk)(?:/|$)")
KEEP_BYTES = 40 << 20      # an .xplane.pb larger than this is not brought back
KEEP_DIR = os.path.join(ROOT, "chiprun_out")


def xplane_pb2():
    """The profiler's own protobuf schema. ``jax.profiler.ProfileData``
    shows an event's stats but not its metadata's, and on a TPU the scope
    lies there (``tf_op``, beside ``hlo_category``, ``flops``, ``source``).
    TensorFlow ships the generated module; it is loaded by path, so that
    TensorFlow itself (which would reach for the chip) is never imported."""
    import importlib.util

    tf = importlib.util.find_spec("tensorflow")
    if tf is None or not tf.submodule_search_locations:
        raise SystemExit("gaps: no tensorflow install to take xplane_pb2 from")
    path = os.path.join(list(tf.submodule_search_locations)[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(path: str):
    """-> (device planes [{"ops": [[name, start_ns, dur_ns, stats]],
    "modules": [[name, start_ns, dur_ns]]}], program host spans [(name,
    start_ns, end_ns)]) of one .xplane.pb; ``stats`` are the string stats
    of the operation's metadata, one dict per distinct operation."""
    from bench.harness import trace as tracelib

    space = xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    devices, host = [], []
    for plane in space.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = plane.event_metadata

        def events(line):
            t0 = line.timestamp_ns
            for ev in line.events:
                yield (meta[ev.metadata_id], t0 + ev.offset_ps // 1000,
                       ev.duration_ps // 1000)

        if plane.name.startswith("/device:"):
            stats = {k: {stat_names.get(st.metadata_id): st.str_value
                         or stat_names.get(st.ref_value, "")
                         for st in em.stats if st.str_value or st.ref_value}
                     for k, em in meta.items()}
            ops, modules = [], []
            for line in plane.lines:
                if line.name == tracelib.OPS_LINE:
                    ops = [[em.display_name or em.name, s, d, stats[em.id]]
                           for em, s, d in events(line)]
                elif line.name == tracelib.MODULES_LINE:
                    modules = [[em.name, s, d] for em, s, d in events(line)]
            if ops:
                devices.append({"ops": ops, "modules": modules})
        else:
            for line in plane.lines:
                for em, s, d in events(line):
                    if em.name.startswith("h2o3."):
                        host.append((em.name, s, s + d))
    return devices, host


def own_ns(ops: list) -> list:
    """Each operation's duration less what the operations nested in it
    take, in the order of ``ops`` sorted by start (outer first)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [ops[i][2] for i in range(len(ops))]
    stack = []                       # indices of the open enclosing ops
    for i in order:
        s, e = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return own


def scope_of(stats: dict):
    """The named scope in an operation's ``tf_op`` (JAX's op name with its
    name stack), or None."""
    m = SCOPE.search(stats.get("tf_op", ""))
    return m.group(1) if m else None


def module_at(modules: list, starts: list, t: int) -> str:
    """The compiled program (``modules`` sorted by start, ``starts`` their
    start times) that was running at ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i][1] + modules[i][2]:
        return re.sub(r"\(\d+\)$", "", modules[i][0])
    return "(no module)"


def tables(devices: list, host: list) -> dict:
    from bench.harness import trace as tracelib

    gaps = collections.Counter()
    scopes = collections.Counter()
    busy = 0
    for dev in devices:
        merged = tracelib._union([(s, s + d) for _n, s, d, _st in dev["ops"]])
        busy += sum(e - s for s, e in merged)
        for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
            if s1 - e0 >= tracelib.GAP_FLOOR_NS:
                gaps[tracelib._name_gap(e0, s1, host)] += s1 - e0
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        for (_name, s, _d, stats), own in zip(dev["ops"],
                                              own_ns(dev["ops"])):
            scopes[(module_at(modules, starts, s),
                    scope_of(stats) or "(none)")] += own
    k = max(len(devices), 1)
    return {"n_devices": len(devices), "busy_s": busy / 1e9 / k,
            "idle_by_span_s": {n: v / 1e9 / k for n, v in
                               gaps.most_common()},
            "device_by_scope_s": [[m, sc, v / 1e9 / k] for (m, sc), v in
                                  scopes.most_common()]}


def report(result: dict, keep: bool = True) -> None:
    """The two tables as text, all of it as ``chiprun_out/gaps_<cell>.json``
    (`keep`) and, without the samples, as the last line."""
    print(f"\n{result['workload']}: device busy {result['busy_s']:.3f} s of "
          f"{result.get('traced_s', float('nan')):.3f} s traced "
          f"({result.get('platform', 'a trace read again')})")
    print("\n(i) idle seconds by program span")
    for n, v in result["idle_by_span_s"].items():
        print(f"  {v:10.4f}  {n}")
    print("\n(ii) device seconds by compiled program and scope")
    for m, sc, v in result["device_by_scope_s"][:40]:
        print(f"  {v:10.4f}  {m:28s} {sc}")
    if keep:
        os.makedirs(KEEP_DIR, exist_ok=True)
        with open(os.path.join(KEEP_DIR, f"gaps_{result['workload']}.json"),
                  "w") as f:
            json.dump(result, f, indent=1)
    result.pop("stat_samples", None)
    print(json.dumps(result), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window; default: BENCHMARK.json's run_seconds")
    ap.add_argument("--manifest", default="BENCHMARK.json")
    ap.add_argument("--cpu-dry-run", action="store_true")
    ap.add_argument("--xplane", default=None,
                    help="read this .xplane.pb again (one that an earlier "
                         "call brought back) and run nothing")
    args = ap.parse_args()
    if args.xplane:
        report(dict(tables(*load(args.xplane)), workload=args.workload,
                    seed=args.seed, xplane=args.xplane), keep=False)
        return 0
    from bench import run as bench_run

    with open(os.path.join(ROOT, args.manifest)) as f:
        manifest = json.load(f)
    cell, cfg, mix = bench_run.find_cell(manifest, args.workload)
    bench_run.place_caches(mix, cfg)
    from bench.harness import phases, trace as tracelib
    from bench.harness.compilewatch import CompileWatch
    from bench.harness.system import System

    system = System(chips=int(cell["chips"]), dry_run=args.cpu_dry_run)
    run = phases.Run(args, cell, cfg, mix, system, CompileWatch(),
                     tracelib.annotate)
    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    out_dir = os.path.join(bench_run.OUT_DIR, "gaps_" + cell["name"])
    try:
        driver.setup(run)
        spec = mix.get("trace", {})
        tracer = tracelib.SliceTracer(out_dir, spec.get("start_s", 0.0),
                                      spec.get("seconds"))
        tracer.arm()
        run.window = driver.window(
            run, float(args.seconds or manifest["run_seconds"]))
        traced_s = tracer.stop()
    finally:
        system.stop()
    paths = sorted(glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise SystemExit("gaps: the slice never began; no .xplane.pb")
    devices, host = load(paths[-1])
    result = dict(tables(devices, host), workload=cell["name"],
                  seed=args.seed, platform=system.device["platform"],
                  traced_s=traced_s, xplane_bytes=os.path.getsize(paths[-1]),
                  window={k: v for k, v in run.window.items()
                          if isinstance(v, (int, float, str))},
                  host_span_names=sorted({n for n, _s, _e in host}))
    result["stat_samples"] = {}
    for dev in devices:
        for name, _s, _d, stats in dev["ops"]:
            if len(result["stat_samples"]) < 8:
                result["stat_samples"].setdefault(name, stats)
    if result["xplane_bytes"] <= KEEP_BYTES:
        os.makedirs(KEEP_DIR, exist_ok=True)
        shutil.copy(paths[-1], os.path.join(
            KEEP_DIR, f"gaps_{cell['name']}.xplane.pb"))
    shutil.rmtree(out_dir, ignore_errors=True)
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())

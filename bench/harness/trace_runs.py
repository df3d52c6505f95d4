"""Runs of compiled programs in the raw profiler trace of a slice, for
readers that need more than the reduced trace keeps: the device seconds a
program spent inside the slice, how many iterations of its loop those
seconds hold, and the runs that lie wholly inside the slice.

All of it is read from the device planes. On each, the slice is the span
of its operations, from the first one's start to the last one's end. A
run of a program (an event of the ``XLA Modules`` line) counts its part
inside that span. It is whole when another operation began before it and
another ended after it: a run the slice cut at either end is the first or
the last thing on its device.

A loop's iterations are counted by the operations of its body. Each runs
once an iteration, and those outside the loop once a run. The body holds
most of a training program's operations, so the median count over the
distinct operations inside the program's runs is the number of iterations
(to within one where the slice cuts an iteration).

A slice can hold millions of operations, so each device's operations are
read once, as a stream, for every program asked about.
"""

from __future__ import annotations

import bisect
import glob
import os
import statistics

from bench.harness import trace as tracelib


def slice_programs(trace_dir: str, needles: dict) -> dict:
    """``programs_in_slice`` of the newest ``.xplane.pb`` under
    trace_dir, reading its device planes alone."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])

    def events(line):
        return ((ev.name, ev.start_ns, ev.duration_ns) for ev in line.events)

    planes = [{"name": p.name,
               "lines": [{"name": ln.name, "events": events(ln)}
                         for ln in p.lines]}
              for p in data.planes if p.name.startswith("/device:")]
    return programs_in_slice({"planes": planes}, needles)


def programs_in_slice(trace: dict, needles: dict) -> dict:
    """{key: {"runs", "seconds", "iterations", "whole_runs",
    "whole_seconds"} of the programs whose name contains needles[key],
    averaged over the devices that ran one; None where no device did}.
    ``trace`` is ``trace.load_xplane``'s form; a line's events may be any
    iterable of (name, start_ns, duration_ns)."""
    found = {k: [] for k in needles}
    for plane in trace["planes"]:
        if not plane["name"].startswith("/device:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        mods = list(lines.get(tracelib.MODULES_LINE, ()))
        runs = {k: sorted((s, s + d) for name, s, d in mods if n in name)
                for k, n in needles.items()}
        starts = {k: [s for s, _e in r] for k, r in runs.items()}
        counts = {k: {} for k in needles}
        lo = hi = None
        for name, s, d in lines.get(tracelib.OPS_LINE, ()):
            lo = s if lo is None or s < lo else lo
            hi = s + d if hi is None or s + d > hi else hi
            for k, r in runs.items():
                i = bisect.bisect_right(starts[k], s) - 1
                if i >= 0 and s < r[i][1]:
                    counts[k][name] = counts[k].get(name, 0) + 1
        if lo is None:
            continue
        for k, r in runs.items():
            inside = [(max(s, lo), min(e, hi)) for s, e in r
                      if min(e, hi) > max(s, lo)]
            if not inside:
                continue
            whole = [(s, e) for s, e in r if s > lo and e < hi]
            found[k].append({
                "runs": len(inside),
                "seconds": sum(e - s for s, e in inside) / 1e9,
                "iterations": statistics.median(counts[k].values())
                if counts[k] else 0,
                "whole_runs": len(whole),
                "whole_seconds": sum(e - s for s, e in whole) / 1e9})
    return {k: {key: sum(d[key] for d in per) / len(per) for key in per[0]}
            if per else None for k, per in found.items()}

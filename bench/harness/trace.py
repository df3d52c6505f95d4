"""Profiler trace of a slice of the window, and its reduction to numbers.

Two stages, so that the reduction can be checked on a small recorded trace
(``bench/tests/recorded_trace.json``) with no profiler and no chip:

  load_xplane(dir)  -> {"planes": [{"name", "lines": [{"name",
                        "events": [[name, start_ns, dur_ns], ...]}]}]}
  reduce(trace)     -> busy seconds per device (union of the intervals in
                       which an operation ran), the traced window, seconds per
                       device operation and per compiled program, and the
                       longest idle gaps named by what the host was doing.

Device planes are the ones whose name starts with ``/device:``; on them the
line ``XLA Ops`` holds one event per operation and ``XLA Modules`` one per
run of a compiled program. Host spans written with ``annotate(name)``
(``jax.profiler.TraceAnnotation``) land on the host planes' thread lines.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
import threading
import time

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_NAMES = ("job_post", "job_poll", "request", "loadgen_wait")
GAP_FLOOR_NS = 50_000          # idle gaps shorter than 50 us are not listed


def annotate(name: str):
    """A host span in the profiler's own trace (no-op cost when no trace
    is being taken)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class SliceTracer:
    """Traces [start_s, start_s + seconds) of the window from a timer
    thread; ``seconds`` None means up to ``stop()``."""

    def __init__(self, out_dir: str, start_s: float, seconds):
        self.out_dir = out_dir
        self.start_s = float(start_s)
        self.seconds = None if seconds is None else float(seconds)
        self._started = threading.Event()
        self._stopped = threading.Event()
        self._quit = threading.Event()
        self._thread = None
        self.t_start = self.t_stop = None

    def _begin(self):
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.t_start = time.perf_counter()
        self._started.set()

    def _end(self):
        import jax

        if self._started.is_set() and not self._stopped.is_set():
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            self._stopped.set()

    def _run(self):
        if self._quit.wait(self.start_s):
            return
        self._begin()
        if self.seconds is not None:
            self._quit.wait(self.seconds)
            self._end()

    def arm(self):
        """Call at the window's start."""
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-slice-tracer")
        self._thread.start()

    def stop(self):
        """Call at the window's end; returns the traced seconds (0 if the
        slice never began)."""
        self._quit.set()
        if self._thread is not None:
            self._thread.join()
        self._end()
        if self.t_start is None:
            return 0.0
        return self.t_stop - self.t_start


def load_xplane(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under trace_dir as plain lists."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if p["name"].startswith("/device:")
            and any(ln["name"] == OPS_LINE and ln["events"]
                    for ln in p["lines"])]


def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def _host_spans(trace: dict, names=HOST_NAMES) -> list:
    spans = []
    for p in trace["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if name in names:
                    spans.append((name, s, s + d))
    return spans


def _name_gap(gs: int, ge: int, spans: list) -> str:
    """The host span that covers most of an idle gap (ties: the shortest
    span, i.e. the most specific)."""
    best, best_cover, best_len = "host_other", 0, None
    for name, s, e in spans:
        cover = min(ge, e) - max(gs, s)
        if cover <= 0:
            continue
        if cover > best_cover or (cover == best_cover and
                                  (e - s) < best_len):
            best, best_cover, best_len = name, cover, e - s
    return best


def reduce(trace: dict, window_s: float = None, top: int = 10) -> dict:
    """-> {"busy_s", "window_s", "device_ops": [[name, s]...],
           "modules": {name: [n runs, seconds]}, "idle_gaps": [[name, s]...],
           "n_devices"}; busy and the tables are averaged over the devices
    that ran anything. ``window_s`` defaults to the span the device events
    cover."""
    planes = _device_planes(trace)
    if not planes:
        return {"busy_s": 0.0, "window_s": float(window_s or 0.0),
                "device_ops": [], "modules": {}, "idle_gaps": [],
                "n_devices": 0}
    spans = _host_spans(trace)
    busy_ns = 0
    ops = {}
    modules = {}
    gaps = {}
    lo, hi = None, None
    for p in planes:
        ev = _line(p, OPS_LINE)
        merged = _union([(s, s + d) for _n, s, d in ev])
        busy_ns += sum(e - s for s, e in merged)
        lo = merged[0][0] if lo is None else min(lo, merged[0][0])
        hi = merged[-1][1] if hi is None else max(hi, merged[-1][1])
        for name, _s, d in ev:
            ops[name] = ops.get(name, 0) + d
        for name, _s, d in _line(p, MODULES_LINE):
            rec = modules.setdefault(name, [0, 0])
            rec[0] += 1
            rec[1] += d
        for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
            if s1 - e0 >= GAP_FLOOR_NS:
                name = _name_gap(e0, s1, spans)
                gaps[name] = gaps.get(name, 0) + (s1 - e0)
    k = len(planes)
    span_s = (hi - lo) / 1e9
    win = float(window_s) if window_s else span_s
    return {
        "busy_s": busy_ns / 1e9 / k,
        "window_s": max(win, span_s),
        "device_ops": [[n, d / 1e9 / k] for n, d in
                       sorted(ops.items(), key=lambda x: -x[1])[:top]],
        "modules": {n: [c // k if c >= k else c, d / 1e9 / k]
                    for n, (c, d) in modules.items()},
        "idle_gaps": [[n, d / 1e9 / k] for n, d in
                      sorted(gaps.items(), key=lambda x: -x[1])[:top]],
        "n_devices": k,
    }


def module_seconds(reduced: dict, needle: str):
    """(runs, seconds) summed over the compiled programs whose name
    contains ``needle``; None when the trace holds none."""
    runs = secs = 0
    for name, (c, d) in reduced.get("modules", {}).items():
        if needle in name:
            runs += c
            secs += d
    return (runs, secs) if runs else None

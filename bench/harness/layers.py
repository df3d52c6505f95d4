"""What the per-layer readers share. A reader that finds nothing to read
returns None, and the harness leaves its metric out of the line; a share of a
roofline is never reported as 0."""

from __future__ import annotations

import importlib
import statistics

from bench.harness import trace as tracelib
from bench.roofline import peaks


def roofline_module(program: str):
    return importlib.import_module(f"bench.roofline.{program}")


def kernel_roofline_pct(run, program: str, **kw):
    """Least time for the runs of ``program`` that the trace holds, over the
    summed device time of that program's runs (``XLA Modules`` events whose
    name contains the configuration's needle for it)."""
    if not run.trace or run.peak is None:
        return None
    needle = run.cfg.get("programs", {}).get(program)
    if not needle:
        return None
    found = tracelib.module_seconds(run.trace, needle)
    if not found:
        return None
    runs, secs = found
    if secs <= 0:
        return None
    need = roofline_module(program).program_needed(run.cfg, run.rows,
                                                        runs, **kw)
    return 100.0 * peaks.least_seconds(need, run.peak) / secs


def step_mfu_pct(run):
    """Least time the chip could take for the work the window finished
    (operations and bytes from shapes), over the window's seconds, as a
    share of one chip's peak times the chips used."""
    if run.peak is None or not run.window:
        return None
    program = run.cfg.get("roofline", {}).get(run.mix.get("activity"))
    if not program:
        return None
    need = roofline_module(program).step_needed(run.cfg, run.rows,
                                                     run.window)
    span = float(run.window.get("span_s") or 0)
    if span <= 0 or (need["flops"] <= 0 and need["bytes"] <= 0):
        return None
    chips = max(int(run.system.device["count"]), 1)
    return 100.0 * peaks.least_seconds(need, run.peak) / (span * chips)


def span_ms_per_trace(run, names) -> list:
    """Per request trace: the summed ms of its spans called one of
    ``names``; traces without any are left out."""
    out = []
    for spans in run.system.spans("ingress"):
        ms = [s["ms"] for s in spans if s.get("name") in names
              and s.get("ms") is not None]
        if ms:
            out.append(sum(ms))
    return out


def median_or_none(values):
    return statistics.median(values) if values else None

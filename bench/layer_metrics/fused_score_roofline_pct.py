"""Roofline share of the fused scoring programs: the rows the window scored
against the summed device time of the programs whose name holds the
configuration's needle (bench/roofline/fused_score.py)."""

from bench.harness import trace as tracelib
from bench.roofline import fused_score, peaks


def read(run, name):
    if not run.trace or run.peak is None:
        return None
    needle = run.cfg.get("programs", {}).get("fused_score")
    found = tracelib.module_seconds(run.trace, needle) if needle else None
    if not found or found[1] <= 0:
        return None
    # rows scored inside the traced slice, pro rata of the window's rows
    share = run.trace["window_s"] / float(run.window["span_s"])
    rows = int(run.window["rows_scored"] * min(share, 1.0))
    if rows <= 0:
        return None
    need = fused_score.program_needed(run.cfg, rows)
    return 100.0 * peaks.least_seconds(need, run.peak) / found[1]

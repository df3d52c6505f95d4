"""Chunk-streamed dispatch: the ONE chokepoint of the OOM ladder.

:func:`run_windows` drives an existing fused program over row-chunk
windows. The caller supplies ``dispatch(pos, m) -> device output`` — the
same pack-then-execute body its single-dispatch loop already runs — and
optionally ``fetch(out, m)`` for paths that block on each window's
output (host scoring). The driver owns everything else:

- **planning** — the initial window size comes from
  ``budget.plan(family, rows)``; an unbudgeted process runs one
  full-size window and the engine is byte-for-byte its pre-planner
  self.
- **double buffering** — dispatch is async in jax, so window ``i+1`` is
  shipped before window ``i``'s output is fetched; the H2D of the next
  chunk overlaps the compute of the current one.
- **the degradation ladder** — a dispatch (or its fetch) that raises
  RESOURCE_EXHAUSTED, or trips the ``mem.exhausted`` faultpoint, first
  asks the cleaner to sweep cold columns off the device, then halves the
  window (floor 1 row) and retries under the bounded PR-3 backoff
  budget. Windows are re-dispatched from their own start position, so a
  recovered ladder is bitwise-identical to an untroubled run (every
  fused program here is row-local by the fusibility contract). Only an
  exhausted budget surfaces :class:`~h2o3_tpu.memory.MemoryPressureError`
  — after a flight record naming the family and the attempted chunk
  sizes, and after flagging pressure so admission sheds instead of
  queueing into the same wall.

Bitwise contract: the driver never changes WHAT a window computes, only
how many rows ride each dispatch — callers' programs are row-local
(bin+walk per row, elementwise statement bodies), so the concatenation
of window outputs equals the single-dispatch output exactly.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, List, Optional

from h2o3_tpu.memory import MemoryPressureError, budget
from h2o3_tpu.obs import tracing
from h2o3_tpu.parallel import retry

_LOCK = threading.Lock()
_COUNTS = {"chunked_runs": 0,        # run_windows calls that windowed
           "windows": 0,             # windows dispatched (all runs)
           "ladder_halvings": 0,     # OOM-triggered window halvings
           "ladder_recoveries": 0,   # runs that hit OOM and completed
           "pressure_failures": 0,   # exhausted ladders
           "spill_retries": 0}       # bounded remote-read retries


def _bump(key: str, n: int = 1) -> None:
    with _LOCK:
        _COUNTS[key] += n


def counters() -> dict:
    with _LOCK:
        return dict(_COUNTS)


def reset_counters() -> None:
    with _LOCK:
        for k in _COUNTS:
            _COUNTS[k] = 0


def is_oom(exc: BaseException) -> bool:
    """Does this exception mean the device ran out of memory? XLA
    surfaces RESOURCE_EXHAUSTED through XlaRuntimeError text; the
    ``mem.exhausted`` faultpoint injects the same condition for chaos
    coverage."""
    from h2o3_tpu.core.failure import InjectedFault

    if isinstance(exc, InjectedFault):
        return "mem.exhausted" in str(exc)
    return "RESOURCE_EXHAUSTED" in str(exc)


def _sweep_cold(need_bytes: int) -> int:
    """Ask the LRU cleaner to evict cold columns (device → host spill)
    before retrying a failed window — the ladder's first rung is freeing
    what the dispatch competes with."""
    try:
        from h2o3_tpu.core import cleaner

        return int(cleaner.sweep(max(int(need_bytes), 1 << 20)))
    except Exception:   # noqa: BLE001 — best-effort relief
        return 0


def run_windows(family: str, n: int, dispatch: Callable[[int, int], Any],
                max_window: int,
                fetch: Optional[Callable[[Any, int], Any]] = None,
                row_bytes: Optional[float] = None,
                window_sizer: Optional[Callable[[int], int]] = None
                ) -> List[Any]:
    """Run `dispatch` over `n` rows in planned windows; returns the list
    of (fetched) window outputs in row order.

    `max_window` is the caller's own dispatch ceiling (the largest row
    bucket); `window_sizer` optionally snaps a planned window down to a
    size the caller has a compiled program for (the bucket ladder), so
    chunking never mints new program shapes."""
    from h2o3_tpu.core import failure

    if n <= 0:
        return []
    # its own span: the plan reads the device's memory statistics and scans
    # the residency of every column in the store, which is milliseconds
    # beside thousands of resident columns
    with tracing.span("plan", family=family) as sp:
        decision = budget.plan(family, n, row_bytes)
        sp.set(mode=decision.mode)
    if decision.mode == "refuse":
        _fail_pressure(family, n, [], decision)
    win = max_window
    if decision.mode == "chunked":
        win = max(min(max_window, decision.chunk_rows), 1)
        _bump("chunked_runs")
    if window_sizer is not None:
        win = max(window_sizer(win), 1)

    pieces: List[Any] = []
    attempts: List[int] = []            # window sizes that OOMed
    delays = None                       # lazily-armed bounded backoff
    pending: Optional[tuple] = None     # (out, pos, m) awaiting fetch
    saw_oom = False
    pos = 0
    while pos < n or pending is not None:
        try:
            if pos < n:
                m = min(win, n - pos)
                # the chaos hook sits exactly where XLA would raise
                failure.faultpoint("mem.exhausted")
                out = dispatch(pos, m)
                _bump("windows")
            else:
                m = 0
                out = None
            # double buffer: window i+1 is in flight; now block on i
            if pending is not None:
                p_out, _p_pos, p_m = pending
                pieces.append(p_out if fetch is None
                              else fetch(p_out, p_m))
                pending = None
            if out is not None:
                if fetch is None:
                    pieces.append(out)
                else:
                    pending = (out, pos, m)
                pos += m
        except Exception as e:   # noqa: BLE001 — only OOM walks the ladder
            if not is_oom(e):
                raise
            saw_oom = True
            # the window being retried: the failed dispatch's own, or the
            # pending one whose fetch surfaced the exhaustion
            if pending is not None:
                pos = pending[1]
                pending = None
            attempts.append(min(win, max(n - pos, 1)))
            if delays is None:
                delays = retry.backoff_delays()
            delay = next(delays, None)
            if delay is None:
                _fail_pressure(family, n, attempts, decision, cause=e)
            _sweep_cold(int(win * decision.row_bytes))
            if win > 1:
                win = max(win // 2, 1)
                if window_sizer is not None:
                    win = max(window_sizer(win), 1)
                _bump("ladder_halvings")
            time.sleep(delay)
    if saw_oom:
        _bump("ladder_recoveries")
    return pieces


def _fail_pressure(family: str, rows: int, attempts: List[int],
                   decision, cause: Optional[BaseException] = None):
    """Exhausted ladder: flight record + pressure flag + typed error."""
    _bump("pressure_failures")
    budget.note_pressure()
    try:
        from h2o3_tpu.obs import flight

        flight.record_flight(
            "mem_pressure",
            extra={"family": family, "rows": int(rows),
                   "chunk_attempts": [int(a) for a in attempts],
                   "budget_bytes": decision.free_bytes,
                   "row_bytes": decision.row_bytes})
    except Exception:   # noqa: BLE001 — postmortem is best-effort
        pass
    tried = ", ".join(str(a) for a in attempts) or "none"
    err = MemoryPressureError(
        f"device memory exhausted dispatching {family!r} over {rows} "
        f"rows; degradation ladder tried windows of [{tried}] rows "
        f"without fitting — retry when resident frames unload",
        retry_after_s=budget.pressure_retry_after_s(),
        family=family, attempts=attempts)
    raise err from cause


# ---------------------------------------------------------------------------
# shared bounded remote-read retry (DKV blob fetches + persist spill reads)
# ---------------------------------------------------------------------------

def bounded_remote_read(fn: Callable[[], Any], what: str):
    """One retry discipline for every read that stands between a
    dispatch and its data: DKV replicated-blob fetches and persist spill
    reloads share the bounded PR-3 backoff budget and the
    ``h2o3_mem_spill_retries_total`` counter, so a flaky S3 backend (or
    coordination KV) degrades LOUDLY — a visible retry ramp then a clean
    error — instead of stalling the dispatch behind an unbounded loop.

    `fn` returns None (or raises OSError/ValueError) on a miss; the last
    attempt's result (or exception) is the caller's to handle."""
    result = fn()
    if result is not None:
        return result
    for delay in retry.backoff_delays():
        _bump("spill_retries")
        from h2o3_tpu.utils.log import get_logger

        get_logger().warning("retrying remote read of %s in %.0f ms",
                             what, delay * 1000.0)
        time.sleep(delay)
        result = fn()
        if result is not None:
            return result
    return result

"""TimeLine event ring + jax.profiler wiring.

Reference: water/TimeLine.java:22 — a per-node lock-free ring of wire events
snapshotted over REST.

TPU-native mapping: the interesting events are no longer UDP packets but XLA
dispatches, plus HBM gauges and the XLA profiler's own trace files. The ring
is process-wide and cheap enough to stay always-on. Where the time of one
request or job goes is the span tree's business (obs/tracing.py), which
needs no device sync and mirrors its spans into a profiler capture."""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, List, Optional

_RING: collections.deque = collections.deque(maxlen=4096)
_LOCK = threading.Lock()

# the closed enumeration of event kinds h2o3_tpu/ may record: free-form
# kind drift makes the ring un-queryable (and un-documentable), so
# tests/test_consistency.py pins every record() call-site literal
# to this set (mirroring the faultpoint-name guard). "rest" is emitted by
# the API layer's request ring merge, not by record().
KINDS = frozenset({
    "artifact",         # AOT artifact export/import
    "cloud",            # supervision/election/rejoin/demotion events
    "flight",           # flight-recorder dumps (obs/flight.py)
    "job",              # durable job-progress saves
    "oplog",            # control-plane checkpoints
    "phase",            # lifecycle phase begin/end (obs/phases.py)
    "profiler",         # /3/Profiler start/stop captures
    "rest",             # REST request ring (api/server.py merge)
    "scoring",          # fused serving dispatches
    "search",           # durable AutoML/grid search-state saves + resumes
    "self_benchmark",   # mesh boot probes
    "xla_trace",        # XLA profiler captures
})

_RESERVED = ("time_ms", "kind", "what", "ms")


def record(kind: str, what: str, ms: Optional[float] = None, **meta) -> None:
    ev = {"time_ms": int(time.time() * 1000), "kind": kind, "what": what}
    if ms is not None:
        ev["ms"] = round(float(ms), 3)
    # reserved keys win: caller meta must not clobber the event's identity
    # fields (a meta dict splatted with e.g. time_ms used to silently
    # overwrite the timestamp) — colliding meta lands under a meta_ prefix
    for k, v in meta.items():
        ev[f"meta_{k}" if k in _RESERVED else k] = v
    with _LOCK:
        _RING.append(ev)


def events(n: Optional[int] = None) -> List[dict]:
    with _LOCK:
        evs = list(_RING)
    return evs[-n:] if n else evs


def clear() -> None:
    with _LOCK:
        _RING.clear()


# -- XLA profiler wiring (reference: opt-in MRTask profiling; here the real
#    hardware story is the XLA trace, viewable in xprof/tensorboard) ---------

@contextlib.contextmanager
def trace(log_dir: str):
    """Capture an XLA profiler trace around a code block (profiler API
    routed through compat.py)."""
    from h2o3_tpu import compat

    compat.profiler_start(log_dir)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        compat.profiler_stop()
        record("xla_trace", log_dir, ms=(time.perf_counter() - t0) * 1000)


def device_memory() -> List[Dict]:
    """Per-device HBM gauges (the per-node memory columns of /3/Cloud;
    water.Cleaner's MemoryManager numbers are the reference analog)."""
    import jax

    out = []
    for d in jax.local_devices():
        stats = {}
        try:
            stats = d.memory_stats() or {}
        except Exception:   # noqa: BLE001 — not all backends implement it
            pass
        out.append({"device": str(d),
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out

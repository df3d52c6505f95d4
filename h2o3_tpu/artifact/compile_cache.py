"""Persistent fused-program compile cache.

The bucketed serving fast path compiles one program per (model, bucket).
Before this module every server restart re-paid that compile spike. Now
each compiled executable is serialized (compat shims) into an on-disk
cache keyed by ``(model checksum, bucket, variant, backend fingerprint)``
under ``$H2O_TPU_COMPILE_CACHE_DIR`` — shared across processes and server
restarts (put it on shared storage for multi-process clouds, exactly like
the oplog checkpoint dir), so a warm restart compiles ZERO fused programs.

Unset env disables the disk tier (sessions still hold executables in
memory for their lifetime). Writes are atomic (tmp + rename), reads are
checksum-free by design — the key embeds the model checksum, and a
corrupt blob simply fails deserialization and falls back to a compile.

The module also owns the fused-compile counter the warm-restart test (and
bench cold-start stage) assert on: ``note_compile()`` increments ONLY when
an actual XLA compilation ran.
"""

from __future__ import annotations

import hashlib
import os
import threading
from typing import Any, Optional

_LOCK = threading.Lock()
_STATS = {"compiles": 0, "disk_hits": 0, "disk_misses": 0, "stores": 0,
          "load_failures": 0, "compile_ms_total": 0.0}


def cache_dir() -> Optional[str]:
    """Cache root (env ``H2O_TPU_COMPILE_CACHE_DIR``); None disables the
    persistent tier."""
    d = os.environ.get("H2O_TPU_COMPILE_CACHE_DIR", "").strip()
    return d or None


def enabled() -> bool:
    return cache_dir() is not None


def cache_key(model_checksum: str, bucket: int, variant: str = "mesh",
              fingerprint: Optional[str] = None) -> str:
    """Filename-safe key. `variant` separates program families compiled
    from the same forest (mesh-sharded serving vs degraded-local vs the
    artifact's single-device lowering)."""
    if fingerprint is None:
        from h2o3_tpu.artifact import aot

        fingerprint = aot.backend_fingerprint()
    raw = f"{model_checksum}|b{int(bucket)}|{variant}|{fingerprint}"
    return hashlib.sha256(raw.encode()).hexdigest()


def _path(key: str) -> Optional[str]:
    d = cache_dir()
    if d is None:
        return None
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"xc_{key}.bin")


_PHASE_LOAD_SEEN = False


def load(key: str) -> Optional[Any]:
    """Loaded executable for `key`, or None (disabled / miss / unloadable
    blob — the caller compiles). The FIRST executable deserialize of the
    process runs inside the ``compile_cache_load`` lifecycle phase: it
    talks to the backend, so a backend that hangs hangs HERE at
    warm-start — the phase tracker's deadline and timeline event make
    that visible instead of silent. Later serving-time loads skip the
    phase so they cannot flood the bounded phase history (the boot
    records must survive a long-lived server)."""
    global _PHASE_LOAD_SEEN

    path = _path(key)
    if path is None:
        return None
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        with _LOCK:
            _STATS["disk_misses"] += 1
        return None
    try:
        from h2o3_tpu.artifact import aot

        if not _PHASE_LOAD_SEEN:
            _PHASE_LOAD_SEEN = True
            from h2o3_tpu.obs import phases

            with phases.enter("compile_cache_load", key=key[:16]):
                exe = aot.load_exec_blob(blob)
        else:
            exe = aot.load_exec_blob(blob)
    except Exception:   # noqa: BLE001 — any unloadable blob = miss
        with _LOCK:
            _STATS["load_failures"] += 1
        return None
    with _LOCK:
        _STATS["disk_hits"] += 1
    return exe


def store(key: str, compiled) -> bool:
    """Best-effort serialize + atomic write; False when disabled or this
    backend cannot serialize executables."""
    path = _path(key)
    if path is None:
        return False
    try:
        from h2o3_tpu.artifact import aot

        blob = aot.serialize_exec_blob(compiled)
        tmp = f"{path}.{os.getpid()}.part"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except Exception:   # noqa: BLE001 — the cache must never fail serving
        return False
    with _LOCK:
        _STATS["stores"] += 1
    return True


def note_compile(ms: float = 0.0) -> None:
    """Record one actual fused-program XLA compilation. Since the compile
    ledger landed, ``obs/compiles.py`` is the ONLY caller (enforced by
    the `compile-ledger` analysis pass): the ledger times the compile
    itself and feeds this counter the SAME milliseconds it recorded in
    the per-program row, so ``compile_ms_total`` can never drift from
    the ledger (it used to be caller-self-reported)."""
    with _LOCK:
        _STATS["compiles"] += 1
        _STATS["compile_ms_total"] += float(ms)


def fused_compile_count() -> int:
    with _LOCK:
        return _STATS["compiles"]


def stats() -> dict:
    with _LOCK:
        out = dict(_STATS)
    out["dir"] = cache_dir()
    out["enabled"] = enabled()
    return out


def reset_stats() -> None:
    """Zero the counters (tests / warm-restart drills)."""
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0

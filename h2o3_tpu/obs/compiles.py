"""Cluster-wide compile ledger: the ONE chokepoint for XLA compilation.

"Automatic Full Compilation of Julia Programs and ML Models to Cloud
TPUs" (PAPERS.md) shows compile time is the dominant, attributable cost
of the XLA path; "Memory Safe Computations with XLA Compiler" motivates
recording each program's memory estimate next to its compile cost. Until
this module those costs were scattered: scoring, rapids fusion and the
artifact exporter each ran ``jit(...).lower(...).compile()`` themselves
and self-reported (or didn't) into ad-hoc counters that could drift.

Now EVERY explicit XLA compile in the repo routes through here
(:func:`compile_jit` / :func:`compile_lowered` / :func:`compile_stablehlo`
— an analysis pass bans direct ``.lower(...).compile(`` /
``compile_stablehlo`` calls outside this module), and each records one
ledger row: program family (closed :data:`FAMILIES` enumeration),
signature hash, wall duration ms, cache disposition
(compile | memory | disk), device kind, and the optional HBM estimate
from ``compiled.memory_analysis()`` (via ``compat.py``). Cache HITS are
recorded by the same chokepoint (:func:`record_hit`), so the per-family
table on ``GET /3/Runtime`` tells hit ratios, not just compile counts.

The legacy ``artifact/compile_cache.note_compile()`` counter is now a
VIEW over this ledger: the ledger times the compile itself and feeds the
counter for the persistent-cache families (scoring/rapids), so
``compile_ms_total`` can never drift from the per-program rows.

Import cost: stdlib only (jax/compat imported per call — by the time
anything compiles, the backend is necessarily up)."""

from __future__ import annotations

import collections
import hashlib
import threading
import time
from typing import Any, Dict, List, Optional

# closed program-family enumeration: scoring = fused bin+traverse serving
# programs, explain = fused bin+leaf explainability programs (leaf
# assignment / staged probabilities), binning = tree-training bin-matrix
# builds, rapids = statement fusion, pipeline = munge→score splices (the
# rapids feature graph + the model core in ONE program), artifact = AOT
# exporter lowerings, pack = sharded data-plane packers, probe = the
# supervised boot first-compile, tree = tree-grower programs (histogram
# builds, grow/apply steps, per-tree pre/post residual math, compressed
# forest traversal — everything a GBM/DRF train compiles), metrics =
# model-metrics accumulation passes (the AUC histogram)
FAMILIES = frozenset({"scoring", "explain", "binning", "rapids", "pipeline",
                      "artifact", "pack", "probe", "tree", "metrics"})

# persistent-compile-cache families whose actual compiles feed the legacy
# note_compile() counter (the warm-restart zero-compile assertions)
_CACHED_FAMILIES = ("scoring", "explain", "rapids", "pipeline")

_KV_PREFIX = "obs/runtime/"

_LOCK = threading.Lock()
_ROWS: "collections.deque[dict]" = collections.deque(maxlen=512)
_AGG: Dict[str, Dict[str, float]] = {}
# (family, tier) -> hit count, bumped LOCK-FREE on the warm dispatch
# path and folded into family_table() at read time
_HIT_COUNTS: Dict[tuple, int] = {}


def _check(family: str) -> None:
    if family not in FAMILIES:
        raise ValueError(f"unknown compile family {family!r}; the "
                         f"enumeration is closed: {sorted(FAMILIES)}")


def _sig(signature: Any) -> str:
    """Stable short hash of whatever signature material the caller has
    (model checksum + bucket, an AST signature, a geometry tuple)."""
    raw = signature if isinstance(signature, str) else repr(signature)
    return hashlib.sha256(raw.encode()).hexdigest()[:16]


def _device_kind() -> Optional[str]:
    """Backend identity for the row; never triggers backend init (at
    compile time it is up by construction, but hit recording may run
    earlier)."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        d = jax.devices()[0]
        return f"{d.platform}/{getattr(d, 'device_kind', '?')}"
    except Exception:   # noqa: BLE001
        return None


def _hbm_estimate(compiled) -> Optional[int]:
    try:
        from h2o3_tpu import compat

        ma = compat.memory_analysis(compiled)
    except Exception:   # noqa: BLE001
        return None
    if not ma:
        return None
    return int(sum(v for v in (ma.get("argument_bytes"),
                               ma.get("output_bytes"),
                               ma.get("temp_bytes"),
                               ma.get("generated_code_bytes")) if v))


def _agg_for(family: str) -> Dict[str, float]:
    a = _AGG.get(family)
    if a is None:
        a = _AGG[family] = {"compiles": 0, "hits_memory": 0, "hits_disk": 0,
                            "ms_total": 0.0, "ms_max": 0.0}
    return a


def _append(row: dict) -> None:
    with _LOCK:
        _ROWS.append(row)
        a = _agg_for(row["family"])
        a["compiles"] += 1
        a["ms_total"] += row["ms"]
        a["ms_max"] = max(a["ms_max"], row["ms"])


def record_compile(family: str, signature: Any, ms: float,
                   program: Optional[str] = None,
                   compiled: Any = None) -> dict:
    """One actual XLA compilation. Normally called by the compile_*
    wrappers below (which time the compile themselves); exposed for the
    one case where the compile happens inside an opaque API."""
    _check(family)
    row = {"ts": time.time(), "family": family, "signature": _sig(signature),
           "ms": round(float(ms), 3), "cache": "compile",
           "device_kind": _device_kind(), "program": program,
           "hbm_bytes": _hbm_estimate(compiled) if compiled is not None
           else None}
    _append(row)
    if family in _CACHED_FAMILIES:
        # the legacy counter becomes a view over the ledger: same ms, one
        # writer, zero drift (tests/test_artifact warm-restart assertions)
        from h2o3_tpu.artifact import compile_cache

        compile_cache.note_compile(row["ms"])
    return row


def record_hit(family: str, signature: Any = None, tier: str = "memory",
               program: Optional[str] = None) -> None:
    """A compile AVOIDED: `tier` is ``memory`` (in-process signature
    cache) or ``disk`` (persistent compile cache). Hits bump the
    per-family aggregate ONLY — they never consume the bounded
    compile-row ring (warm traffic would otherwise evict every
    ``cache="compile"`` row and empty /3/Runtime's slowest-N on exactly
    the long-lived clusters it exists for), and the warm path pays no
    signature hashing or device lookup. `signature`/`program` are
    accepted for call-site symmetry with the compile entries."""
    _check(family)
    if tier not in ("memory", "disk"):
        raise ValueError(f"unknown cache tier {tier!r}")
    # lock-free counter bump: this runs once per warm fused dispatch (the
    # hottest path in the engine), which must not serialize on the same
    # process-wide lock compile recording and /3/Runtime snapshots take.
    # A GIL-raced lost increment on an observability ratio is acceptable;
    # family_table() folds these in at read time.
    k = (family, tier)
    _HIT_COUNTS[k] = _HIT_COUNTS.get(k, 0) + 1


# ---------------------------------------------------------------------------
# every backend compile, whoever asked for it
# ---------------------------------------------------------------------------

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_WATCHING = False


def _on_backend_compile(event: str, secs: float, fun_name=None,
                        **_kw) -> None:
    """``jax.monitoring`` calls this on the compiling thread, right after
    the compile: the ledger above sees only its own call sites, this sees
    eager ops and bare jits too (and a load from JAX's persistent cache,
    which the event wraps as well: a short one). Under an active span the
    compile becomes its child ``compile``, so ``/3/Trace/{id}`` names the
    step that recompiled, and ``program`` (JAX's ``fun_name``: ``jit(run)``,
    an eager op's ``jit(concatenate)``) what it compiled. An attribute and
    not a counter label: names are unbounded."""
    if event != BACKEND_COMPILE_EVENT:
        return
    from h2o3_tpu.obs import metrics, tracing

    metrics.inc("h2o3_backend_compiles_total")
    metrics.inc("h2o3_backend_compile_seconds_total", secs)
    ctx = tracing.context()
    if ctx is not None:
        end = tracing.now_ms()
        tracing.record_span("compile", ctx, end - secs * 1000.0, end,
                            seconds=round(secs, 6),
                            program=str(fun_name) if fun_name else None)


def watch_backend_compiles() -> None:
    """Register the listener above, once per process (the boot calls this
    before its first compile; JAX has no way to take a listener back)."""
    global _WATCHING
    with _LOCK:
        if _WATCHING:
            return
        _WATCHING = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(
        _on_backend_compile)


# ---------------------------------------------------------------------------
# the chokepoint entries (the ONLY legal spellings of an XLA compile —
# enforced by the `compile-ledger` analysis pass)
# ---------------------------------------------------------------------------

def compile_jit(family: str, jfn, args, signature: Any = None,
                program: Optional[str] = None):
    """Lower + compile a ``jax.jit`` wrapper over `args` (concrete arrays
    or ShapeDtypeStructs), timing the compile HERE so no caller
    self-reports a duration the ledger didn't measure."""
    _check(family)
    t0 = time.perf_counter()
    compiled = jfn.lower(*args).compile()
    ms = (time.perf_counter() - t0) * 1000
    record_compile(family, signature if signature is not None else program,
                   ms, program=program, compiled=compiled)
    return compiled


def compile_lowered(family: str, lowered, signature: Any = None,
                    program: Optional[str] = None):
    """Compile an already-lowered program (the artifact exporter keeps
    the lowering to also serialize its StableHLO text)."""
    _check(family)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    ms = (time.perf_counter() - t0) * 1000
    record_compile(family, signature if signature is not None else program,
                   ms, program=program, compiled=compiled)
    return compiled


def compile_stablehlo(family: str, text: str, signature: Any = None,
                      program: Optional[str] = None):
    """Compile StableHLO module text through the local XLA client
    (compat-shimmed), ledger-recorded like every other compile."""
    _check(family)
    from h2o3_tpu import compat

    t0 = time.perf_counter()
    exe = compat.compile_stablehlo(text)
    ms = (time.perf_counter() - t0) * 1000
    record_compile(family, signature if signature is not None else text[:256],
                   ms, program=program)
    return exe


# a key whose AOT lowering failed (or whose executable rejected a call):
# dispatch through the plain jit wrapper from then on. Distinct sentinel —
# None would be ambiguous with a missing key under dict.get.
_JIT_FALLBACK = object()


def _arg_key(args) -> str:
    """Shape/dtype signature of a call's arguments. Array leaves key by
    (shape, dtype); non-array leaves (python scalars, bools) key by TYPE
    only — jit treats them as weak-typed dynamic args, so keying their
    values would recompile per learning-rate/sample-rate value."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append((tuple(shape), str(dtype)))
        else:
            parts.append(type(leaf).__name__)
    return repr((parts, str(treedef)))


class _LedgeredJit:
    """A ``jax.jit`` wrapper whose every compile lands in the ledger.

    First call per argument shape class AOT-compiles through
    :func:`compile_jit` (one timed ledger row); subsequent calls hit the
    executable cache and bump :func:`record_hit` — so a warm re-train
    adds ZERO compile rows. Shapes the AOT path cannot serve (lowering
    failure, or an executable rejecting a call over sharding/weak-type
    drift) permanently fall back to the plain jit wrapper for that key.
    ``lower`` passes through, so callers that AOT-compile under their own
    family (scoring's executable cache over compressed-forest programs)
    keep working."""

    def __init__(self, family, fn, program=None, jit_kw=None):
        import jax

        _check(family)
        self._family = family
        self._program = program
        self._jfn = jax.jit(fn, **(jit_kw or {}))
        self._exe: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def lower(self, *args, **kw):
        return self._jfn.lower(*args, **kw)

    def __call__(self, *args):
        key = _arg_key(args)
        exe = self._exe.get(key)
        if exe is None:
            with self._lock:
                exe = self._exe.get(key)
                if exe is None:
                    try:
                        exe = compile_jit(self._family, self._jfn, args,
                                          signature=key,
                                          program=self._program)
                    except Exception:   # noqa: BLE001 — AOT-hostile shape
                        exe = _JIT_FALLBACK
                    self._exe[key] = exe
        else:
            record_hit(self._family, tier="memory")
        if exe is _JIT_FALLBACK:
            return self._jfn(*args)
        try:
            return exe(*args)
        except Exception:   # noqa: BLE001 — input layout the AOT
            # executable can't accept (sharding / weak-type drift):
            # this key dispatches through plain jit from now on
            self._exe[key] = _JIT_FALLBACK
            return self._jfn(*args)


def ledgered_jit(family: str, fn, program: Optional[str] = None, **jit_kw):
    """``jax.jit(fn)`` with ledger-visible compiles: the legal spelling
    of a jit under the ``jax.jit`` ban scopes (models/tree/). Keyword
    args pass through to ``jax.jit``."""
    return _LedgeredJit(family, fn, program=program, jit_kw=jit_kw)


# ---------------------------------------------------------------------------
# snapshots / cluster aggregation (GET /3/Runtime)
# ---------------------------------------------------------------------------

def ledger_rows(n: Optional[int] = None) -> List[dict]:
    with _LOCK:
        rows = list(_ROWS)
    return rows[-n:] if n else rows


def family_table() -> Dict[str, Dict[str, float]]:
    with _LOCK:
        out = {f: dict(a) for f, a in _AGG.items()}
    for (fam, tier), n in list(_HIT_COUNTS.items()):
        a = out.setdefault(fam, {"compiles": 0, "hits_memory": 0,
                                 "hits_disk": 0, "ms_total": 0.0,
                                 "ms_max": 0.0})
        a["hits_memory" if tier == "memory" else "hits_disk"] = n
    return out


def slowest(n: int = 10) -> List[dict]:
    rows = [r for r in ledger_rows() if r["cache"] == "compile"]
    return sorted(rows, key=lambda r: r["ms"], reverse=True)[:max(n, 0)]


def snapshot(slowest_n: int = 10) -> dict:
    return {"families": family_table(), "slowest": slowest(slowest_n),
            "rows_recorded": len(ledger_rows())}


def _proc_index() -> int:
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return 0
    try:
        return int(jax.process_index())
    except Exception:   # noqa: BLE001
        return 0


def runtime_snapshot(slowest_n: int = 10) -> dict:
    """This process's /3/Runtime contribution: phase summary + ledger.
    The full phase-history ring deliberately stays OUT of this payload —
    it is KV-published every ~2 s per process and nothing reads it from
    the merged snapshots (the coordinator serves its own history live);
    ``phase_report`` carries the per-phase durations that ARE consumed."""
    from h2o3_tpu.obs import phases

    return {"proc": _proc_index(), "ts": time.time(),
            "phase_report": phases.phase_report(),
            "compiles": snapshot(slowest_n)}


def publish_runtime() -> bool:
    """KV-publish this process's runtime snapshot (piggybacked on the
    metrics publish throttle) so the coordinator's /3/Runtime is
    cluster-wide."""
    import json

    from h2o3_tpu.parallel import distributed as D

    try:
        return D.kv_put(_KV_PREFIX + str(_proc_index()),
                        json.dumps(runtime_snapshot(), default=str))
    except Exception:   # noqa: BLE001 — best-effort by contract
        return False


def cluster_runtime(slowest_n: int = 10) -> List[dict]:
    """Own LIVE snapshot + every other process's KV-published one. The
    live snapshot honors `slowest_n`; remote rows carry their publish
    default (10)."""
    import json

    from h2o3_tpu.parallel import distributed as D

    me = _proc_index()
    out = [runtime_snapshot(slowest_n)]
    try:
        rows = list(D.kv_dir(_KV_PREFIX))
    except Exception:   # noqa: BLE001
        rows = []
    for _k, v in rows:
        try:
            rec = json.loads(v)
        except (ValueError, TypeError):
            continue
        if isinstance(rec, dict) and rec.get("proc") != me:
            out.append(rec)
    return out


def merge_family_tables(tables: List[Dict[str, dict]]) -> Dict[str, dict]:
    """Sum per-family aggregates across processes (ms_max takes max)."""
    merged: Dict[str, dict] = {}
    for table in tables:
        for fam, a in (table or {}).items():
            m = merged.setdefault(fam, {"compiles": 0, "hits_memory": 0,
                                        "hits_disk": 0, "ms_total": 0.0,
                                        "ms_max": 0.0})
            for k in ("compiles", "hits_memory", "hits_disk", "ms_total"):
                m[k] += a.get(k, 0)
            m["ms_max"] = max(m["ms_max"], a.get("ms_max", 0.0))
    return merged


def reset_for_tests() -> None:
    with _LOCK:
        _ROWS.clear()
        _AGG.clear()
    _HIT_COUNTS.clear()

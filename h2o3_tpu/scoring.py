"""Serving fast path: compile-once, device-resident scoring sessions.

Reference: H2O-3 solves high-QPS serving with standalone MOJO scorers
(genmodel) that keep the tree bytes resident and score without touching
the training stack. The TPU-native equivalent is a per-model
:class:`ScoringSession` that keeps the CompressedForest arrays
device-resident and dispatches ONE fused XLA program (bin + traverse +
init margin) per request batch.

Two properties make this a serving engine rather than a batch scorer
(cf. "Memory Safe Computations with XLA Compiler" / Podracer, PAPERS.md):

- **Shape buckets**: incoming batches are padded to power-of-two row
  buckets (env ``H2O_TPU_SCORE_BUCKETS``, default 256/1k/4k/16k), so the
  traversal compiles once per (bucket, forest-shape) instead of once per
  request row count. Requests above the largest bucket are chunked at it,
  keeping the trace count bounded. Padded rows are zero-filled and sliced
  off before anything reads them.
- **Micro-batching**: concurrent requests against the SAME model coalesce
  into one flush (one window loop) inside a time-boxed window
  (``H2O_TPU_SCORE_BATCH_WINDOW_MS``, default 2 ms); each request gets its
  exact row-slice back. Requests against different models never block
  each other (per-model queues). On a multi-process cloud the whole batch
  ships as ONE oplog op ("score_batch") that followers replay once.

Per-model serving metrics (requests, batch sizes, latency percentiles,
traversal compile count) land in the timeline ring and are snapshotted by
``GET /3/ScoringMetrics``.
"""

from __future__ import annotations

import bisect
import collections
import functools
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from h2o3_tpu.obs import tracing

_DEFAULT_BUCKETS = (256, 1024, 4096, 16384)


@functools.lru_cache(maxsize=1024)
def _lay_out_fn(lens: tuple, padded: int, tail: tuple, sharding):
    """(n, *outs) -> one entry's margins as its frame's rows, row-sharded:
    window output j gives its first ``lens[j]`` rows, back to back, zeros
    fill out to `padded` rows, and every row from n on is exactly 0.0 (the
    pad `_lift_entry_margins` writes). Its shapes follow the windows and
    the frame, never n, so one compile serves every row count that windows
    alike."""
    import jax.numpy as jnp

    from h2o3_tpu.obs import compiles

    def lay_out(n, *outs):
        x = jnp.concatenate([o[:v] for o, v in zip(outs, lens)]) if outs \
            else jnp.zeros((0,) + tail, jnp.float32)
        if x.shape[0] >= padded:
            x = x[:padded]
        else:
            x = jnp.pad(x, ((0, padded - x.shape[0]),) + ((0, 0),) * len(tail))
        keep = jnp.arange(padded, dtype=jnp.int32) < n
        return jnp.where(keep.reshape((padded,) + (1,) * len(tail)), x,
                         jnp.float32(0))

    return compiles.ledgered_jit("pack", lay_out, program="lay_out_margins",
                                 out_shardings=sharding)

# -- per-process fused-dispatch accounting ----------------------------------
# one increment per fused program execution on the serving/explainability
# paths, by path label (sharded | host | local | leaf_sharded | leaf_host).
# /3/ScoringMetrics serves these under ``dispatches`` and /3/Metrics as
# ``h2o3_score_dispatches_total``; the consistency suite asserts a
# multi-entry sharded flush records exactly one dispatch per row bucket.

_DISP_LOCK = threading.Lock()
_DISPATCHES: Dict[str, int] = {}


def note_dispatch(path: str, n: int = 1) -> None:
    with _DISP_LOCK:
        _DISPATCHES[path] = _DISPATCHES.get(path, 0) + int(n)


def dispatch_counters() -> Dict[str, int]:
    with _DISP_LOCK:
        return dict(_DISPATCHES)


def reset_dispatch_counters() -> None:
    with _DISP_LOCK:
        _DISPATCHES.clear()


def _shard_owners(arr) -> list:
    """Process indices (other than ours) owning shards of a device array —
    the processes a degraded cloud would need to reach to score it."""
    import jax

    try:
        me = jax.process_index()
        return sorted({d.process_index for d in arr.sharding.device_set}
                      - {me})
    except Exception:   # noqa: BLE001 — sharding introspection best-effort
        return []


def _env_buckets() -> Tuple[int, ...]:
    raw = os.environ.get("H2O_TPU_SCORE_BUCKETS", "")
    if not raw.strip():
        return _DEFAULT_BUCKETS
    try:
        vals = sorted({int(v) for v in raw.replace(";", ",").split(",")
                       if v.strip()})
    except ValueError:
        return _DEFAULT_BUCKETS
    return tuple(v for v in vals if v > 0) or _DEFAULT_BUCKETS


def _window_s() -> float:
    try:
        ms = float(os.environ.get("H2O_TPU_SCORE_BATCH_WINDOW_MS", "2"))
    except ValueError:
        ms = 2.0
    return max(ms, 0.0) / 1000.0


def enabled() -> bool:
    return os.environ.get("H2O_TPU_SCORE_FAST", "1").lower() not in (
        "0", "false", "off")


def supports(model) -> bool:
    """True when `model` can ride the fused bucketed path: a SharedTree
    forest whose raw-prediction semantics are pure margin post-processing
    (subclasses overriding _predict_raw — e.g. IsolationForest's
    mean-length output — stay on the generic path)."""
    if not enabled():
        return False
    from h2o3_tpu.models.tree.shared_tree import SharedTreeModel

    return (isinstance(model, SharedTreeModel)
            and model.forest is not None and model.spec is not None
            and type(model)._predict_raw is SharedTreeModel._predict_raw)


class SessionStats:
    """Per-model serving counters behind a small lock; p50/p99 computed at
    read time over a bounded latency ring."""

    def __init__(self):
        self._lock = threading.Lock()
        self.requests = 0
        self.batches = 0
        self.rows = 0
        self.dispatches = 0          # fused program executions (all paths)
        self.max_batch_requests = 0
        self._lat_ms: collections.deque = collections.deque(maxlen=512)

    def record_batch(self, n_requests: int, n_rows: int, ms: float,
                     dispatches: int = 0) -> None:
        with self._lock:
            self.requests += n_requests
            self.batches += 1
            self.rows += n_rows
            self.dispatches += int(dispatches)
            self.max_batch_requests = max(self.max_batch_requests, n_requests)
            self._lat_ms.append(float(ms))

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            lat = np.asarray(self._lat_ms, np.float64)
            out = {"requests": self.requests, "batches": self.batches,
                   "rows": self.rows, "dispatches": self.dispatches,
                   "max_batch_requests": self.max_batch_requests}
            if self.batches:
                out["dispatches_per_flush"] = round(
                    self.dispatches / self.batches, 3)
        if lat.size:
            out["p50_ms"] = round(float(np.percentile(lat, 50)), 3)
            out["p99_ms"] = round(float(np.percentile(lat, 99)), 3)
        return out


class ScoringSession:
    """Device-resident scorer for ONE trained forest.

    Holds the forest arrays + BinSpec tables on device and a fused
    bin+traverse program compiled per row bucket. All margins it returns
    are bitwise-identical to spec.bin_columns + forest.predict_binned."""

    def __init__(self, model):
        import jax.numpy as jnp

        from h2o3_tpu.core.runtime import cluster
        from h2o3_tpu.models.tree.compressed import _fused_score_fn

        self.model = model
        self.forest = model.forest
        self.spec = model.spec
        self._cl = cluster()
        self._arrays = self.forest.arrays()          # device-resident
        self._edges = jnp.asarray(self.spec.padded_edges())
        self._is_cat = jnp.asarray(np.asarray(self.spec.is_cat, bool))
        if self.forest.init_class is not None:
            self._init = jnp.asarray(np.asarray(self.forest.init_class,
                                                np.float32))
        else:
            self._init = jnp.float32(self.forest.init_f)
        # buckets rounded up to shard-divisible sizes so row sharding holds
        self.buckets = tuple(sorted({self._cl.pad_rows(b)
                                     for b in _env_buckets()}))
        self._fn = _fused_score_fn(self.forest.max_depth,
                                   self.forest.nclasses,
                                   self.forest.per_class_trees)
        self._fn_sharded = None          # lazy shard_map'd twin (sharded plane)
        self._fn_leaf = None             # lazy fused bin+leaf twin (explain)
        self._fn_leaf_sharded = None     # ... and its shard_map'd variant
        self._traced: set = set()        # buckets activated so far
        # AOT executables per (bucket, local): dispatched explicitly so
        # compilation is observable (fused-compile counter) and cacheable
        # across server restarts (artifact/compile_cache.py)
        self._exec: Dict[tuple, Any] = {}
        self._model_ck: Optional[str] = None
        self.fused_compiles = 0          # actual XLA compiles this session
        self.cache_hits = 0              # executables served from disk
        self._local_cache = None         # degraded-mode forest array copies
        self.stats = SessionStats()

    # -- feature packing ---------------------------------------------------
    def _features(self, adapted, n: int) -> np.ndarray:
        """(n, F) float32 host matrix in training-column order: numerics
        as-is (NaN = NA), categoricals as their (already remapped) integer
        codes — NA_CAT stays negative and bins to the NA bin.

        This is the HOST-GATHER fallback (degraded-local serving, ragged
        layouts): every column round-trips through this process's host, so
        the rows count as ``gathered`` on the data-plane counters. The
        default serving path packs shard-locally via _sharded_view /
        _margins_sharded_batch and never lands here."""
        from h2o3_tpu.core import sharded_frame

        sharded_frame.note_gathered(n)
        with tracing.span("pack", rows=n, path="host"):
            X = np.empty((n, self.spec.F), np.float32)
            for i, name in enumerate(self.spec.names):
                X[:, i] = np.asarray(adapted.col(name).data)[:n]
        return X

    def _sharded_view(self, adapted):
        """ShardedFrame view of an adapted frame over the training feature
        columns, or None when shard-local packing cannot hold (plane off,
        host-resident column, ragged layout)."""
        from h2o3_tpu.core.sharded_frame import ShardedFrame

        return ShardedFrame.of(adapted, self.spec.names)

    def _note_dispatch(self, path: str) -> None:
        """One dispatch of a fused program: its path, and the form of the
        forest walk it ran (host-side counts, no sync)."""
        note_dispatch(path)
        self.forest.count_walk()

    def _bucket_for(self, m: int) -> int:
        for b in self.buckets:
            if b >= m:
                return b
        return self.buckets[-1]

    def _window_snap(self, w: int) -> int:
        """Snap a planner-chosen window DOWN to a size that divides the top
        bucket, so chunk streaming reuses the compiled bucket programs: the
        largest bucket that is the top bucket over a power of two, else the
        largest such quotient, else 1. Every size so chosen divides every
        larger one, so whatever the planner and the OOM ladder pick, a
        window starts at a multiple of its own size and never crosses a
        multiple of the top bucket (_margins_sharded_batch's entries)."""
        top = self.buckets[-1]
        sizes = [top]
        while sizes[-1] % 2 == 0:
            sizes.append(sizes[-1] // 2)
        sizes.append(1)
        for b in reversed(self.buckets):
            if b <= w and b in sizes:
                return b
        return next(d for d in sizes if d <= max(w, 1))

    def _row_bytes_hint(self) -> float:
        """Static working-set bytes/row for one fused dispatch: packed
        features in and out of the pack program plus the margin lanes —
        the planner takes the max of this and the ledger-seeded
        estimate."""
        F = max(len(self.spec.names), 1)
        return 4.0 * (2 * F + self._out_k() + 2)

    # -- bucketed dispatch -------------------------------------------------
    def _local_arrays(self):
        """Coordinator-local copies of the device-resident forest arrays
        for degraded-cloud serving: the training-time originals may be laid
        out over the GLOBAL mesh, and any dispatch against that mesh is an
        SPMD program a dead follower will never join. Host-roundtripped
        once per session and cached; raises when the arrays themselves need
        the dead peer."""
        if self._local_cache is None:
            import jax.numpy as jnp

            from h2o3_tpu.core.failure import ShardUnavailableError

            for a in self._arrays:
                if not getattr(a, "is_fully_addressable", True):
                    raise ShardUnavailableError(
                        f"cloud degraded and model {self.model.key}'s "
                        "forest arrays are not fully addressable here",
                        owners=_shard_owners(a))
            self._local_cache = tuple(jnp.asarray(np.asarray(a))
                                      for a in self._arrays)
        return self._local_cache

    def _model_checksum(self) -> str:
        if self._model_ck is None:
            from h2o3_tpu.artifact import packer

            self._model_ck = packer.model_checksum(self.forest, self.spec)
        return self._model_ck

    def _sharded_score_fn(self):
        """Lazy shard_map'd twin of the fused program (compressed.py
        _fused_score_sharded_fn) — same per-row core, margins computed per
        addressable row shard under the named 'rows' axis."""
        if self._fn_sharded is None:
            from h2o3_tpu.models.tree.compressed import \
                _fused_score_sharded_fn

            self._fn_sharded = _fused_score_sharded_fn(
                self.forest.max_depth, self.forest.nclasses,
                self.forest.per_class_trees, self._cl.mesh)
        return self._fn_sharded

    def _leaf_score_fn(self, sharded: bool):
        """Lazy fused bin+leaf programs (compressed.py _fused_leaf_fn /
        _fused_leaf_sharded_fn) — the explainability twins of the scoring
        programs, sharing the binning and walk cores bitwise."""
        if sharded:
            if self._fn_leaf_sharded is None:
                from h2o3_tpu.models.tree.compressed import \
                    _fused_leaf_sharded_fn

                self._fn_leaf_sharded = _fused_leaf_sharded_fn(
                    self.forest.max_depth, self._cl.mesh)
            return self._fn_leaf_sharded
        if self._fn_leaf is None:
            from h2o3_tpu.models.tree.compressed import _fused_leaf_fn

            self._fn_leaf = _fused_leaf_fn(self.forest.max_depth)
        return self._fn_leaf

    def _executable_for(self, bucket: int, local: bool, call_args: tuple,
                        sharded: bool = False, kind: str = "score"):
        """AOT executable for one (kind, bucket, placement) — in-memory
        first, then the persistent compile cache
        ($H2O_TPU_COMPILE_CACHE_DIR, keyed by model checksum + bucket +
        variant + backend fingerprint), and only then an actual XLA
        compile (counted, and stored back for the next process/restart).
        A warm restart therefore compiles zero fused programs. `sharded`
        selects the shard_map'd program family (the sharded data plane's
        serving path); `kind` is ``score`` (fused bin+traverse margins,
        ledger family "scoring") or ``leaf`` (fused bin+leaf walk for the
        explainability outputs, ledger family "explain")."""
        key = (kind, bucket, bool(local), bool(sharded))
        family = "scoring" if kind == "score" else "explain"
        exe = self._exec.get(key)
        if exe is not None:
            # warm path: a counter bump only (no ring row, no hashing) —
            # /3/Runtime's scoring hit ratio must reflect the dominant
            # in-memory tier, not just the disk tier
            from h2o3_tpu.obs import compiles

            compiles.record_hit(family, tier="memory")
            return exe
        from h2o3_tpu.artifact import compile_cache
        from h2o3_tpu.obs import compiles

        variant = "local" if local else "sharded" if sharded else "mesh"
        if kind != "score":
            variant = f"{kind}_{variant}"
        progname = f"fused_score_{variant}" if kind == "score" \
            else f"fused_{variant}"
        sig = (str(getattr(self.model, "key", id(self))), bucket, variant)
        ckey = None
        if compile_cache.enabled():
            # checksum + key work only when a persistent tier exists —
            # with the cache off the first dispatch must not pay a
            # whole-forest hash for a key nobody will read
            ckey = compile_cache.cache_key(
                self._model_checksum(), bucket, variant=variant)
            exe = compile_cache.load(ckey)
        if exe is None:
            if kind == "score":
                fn = self._sharded_score_fn() if sharded else self._fn
            else:
                fn = self._leaf_score_fn(sharded)
            # the ledger chokepoint lowers, compiles, times, records the
            # row AND feeds the legacy note_compile counter — callers no
            # longer self-report durations that could drift
            exe = compiles.compile_jit(family, fn, call_args,
                                       signature=sig, program=progname)
            self.fused_compiles += 1
            if ckey is not None:
                compile_cache.store(ckey, exe)
        else:
            self.cache_hits += 1
            compiles.record_hit(family, sig, "disk", program=progname)
        # seed the memory planner's bytes/row estimate from the real
        # lowered program (compat.memory_analysis via the ledger's shim)
        from h2o3_tpu.memory import budget as membudget

        membudget.note_compiled(family, bucket, exe)
        self._exec[key] = exe
        if kind == "score":
            self._traced.add(bucket)
        return exe

    def _margin_x(self, X: np.ndarray, local: bool = False,
                  dispatched: Optional[list] = None) -> np.ndarray:
        """Margins for an (n, F) feature matrix via bucketed fused
        dispatch; returns host (n,) or (n, K) float32, exact per row.
        Rows beyond the largest bucket are chunked at it, so the set of
        compiled traversal programs never exceeds len(self.buckets).
        `local=True` (degraded-cloud serving on a real multi-process cloud)
        dispatches on this process's default device with NO mesh sharding —
        the global row sharding would be a collective the dead peer never
        runs. `dispatched` (a mutable list) receives one bucket entry per
        fused dispatch, so per-model stats count exactly what ran instead
        of re-deriving the chunking arithmetic."""
        import jax

        from h2o3_tpu.memory import stream

        n = X.shape[0]
        maxb = self.buckets[-1]
        sharding = None if local else self._cl.row_sharding()
        arrays = self._local_arrays() if local else self._arrays

        def dispatch(pos: int, m: int):
            bucket = self._bucket_for(m)
            buf = np.zeros((bucket, X.shape[1]), np.float32)
            buf[:m] = X[pos: pos + m]
            xd = jax.device_put(buf) if local else jax.device_put(buf,
                                                                  sharding)
            call_args = (xd, self._edges, self._is_cat, self._init) + \
                tuple(arrays)
            exe = self._executable_for(bucket, local, call_args)
            with tracing.span("dispatch", bucket=bucket, rows=m,
                              path="host"):
                out = exe(*call_args)
            self._note_dispatch("local" if local else "host")
            if dispatched is not None:
                dispatched.append(bucket)
            return out

        def fetch(out, m: int):
            with tracing.span("fetch", rows=m, path="host"):
                return np.asarray(out)[:m]   # the one blocking transfer

        # chunk-streamed under the memory planner: window i+1 ships while
        # window i's output transfers; an OOM walks the halving ladder
        outs: List[np.ndarray] = stream.run_windows(
            "scoring", n, dispatch, maxb, fetch=fetch,
            row_bytes=self._row_bytes_hint(),
            window_sizer=self._window_snap)
        if not outs:
            K = (self.forest.nclasses if (self.forest.nclasses > 2
                                          or self.forest.per_class_trees)
                 else 1)
            return np.zeros((0,) if K == 1 else (0, K), np.float32)
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    def _out_k(self) -> int:
        return (self.forest.nclasses if (self.forest.nclasses > 2
                                         or self.forest.per_class_trees)
                else 1)

    def _margins_sharded_batch(self, items) -> Tuple[List[Any], int]:
        """Fused margins for ALL sharded-eligible entries of one flush:
        ``items`` is ``[(sf, n)]`` in flush order; returns (margins,
        dispatches): per entry, its margins laid out as its frame's rows —
        (padded_rows,) or (padded_rows, K), row-sharded, exactly 0.0 from
        row n on — and the count of fused program executions.

        One window loop a flush, whatever the number of entries: entry i's
        rows start at ``off_i = sum(ceil(n_j / maxb) * maxb for j < i)`` of
        one row space, and every window size divides the top bucket
        (:meth:`_window_snap`), so no window straddles two entries. A
        window is two compiled programs back to back, ``pack_features``
        on the owning entry's chunk and the fused score program on its
        output; no eager device op runs between two windows, and a window
        that lies wholly in an entry's pad dispatches nothing. Each
        entry's outputs are then laid out by one compiled program
        (:func:`_lay_out_fn`) whose shapes follow the windows and the
        frame, never the row count: a flush of any combination of row
        counts compiles nothing once its buckets are warm.

        Bitwise contract: the fused program is row-local (bin + walk per
        row), so every logical row's margin is independent of which window
        carried it — rows [0, n_i) equal the host-packed path's margins
        per entry; pad rows are exactly 0.0, as `_lift_entry_margins`
        pads."""
        from h2o3_tpu.memory import stream
        from h2o3_tpu.obs import metrics as obs_metrics

        maxb = self.buckets[-1]
        n_disp = 0
        arm = "single" if len(items) == 1 else "coalesced"
        offs, end = [], 0
        for _sf, n in items:
            offs.append(end)
            end += -(-n // maxb) * maxb
        rows = offs[-1] + items[-1][1]      # the last entry needs no pad

        def window(pos: int, m: int):
            nonlocal n_disp
            e = bisect.bisect_right(offs, pos) - 1
            sf, n = items[e]
            p = pos - offs[e]
            r = min(m, n - p)
            if r <= 0:
                return ()                   # in the entry's pad: no rows
            bucket = self._bucket_for(r)
            Xd = sf.pack_features(p, n, bucket)
            call_args = (Xd, self._edges, self._is_cat, self._init) + \
                tuple(self._arrays)
            exe = self._executable_for(bucket, False, call_args,
                                       sharded=True)
            # host-side dispatch wall time only — the program is async and
            # NO block_until_ready is added here (the fused-path counters
            # assert the path is unchanged when profiling is off)
            with tracing.span("dispatch", bucket=bucket, rows=r,
                              path="sharded"):
                out = exe(*call_args)
            n_disp += 1
            self._note_dispatch("sharded")
            return e, out, min(m, bucket), bucket

        # ONE span over the whole window loop, never one a window: its self
        # time is what the host does between two programs (the executable
        # lookups and the loop); the planner's `plan` and each window's
        # `pack` and `dispatch` spans lie beneath it
        with tracing.span("windows", arm=arm, entries=len(items)) as ws:
            done = stream.run_windows(
                "scoring", rows, window, maxb,
                row_bytes=self._row_bytes_hint(),
                window_sizer=self._window_snap)
            ws.set(windows=n_disp, rebucket_ms=0.0)
        obs_metrics.inc("h2o3_score_flush_windows_total", n_disp, arm=arm)
        obs_metrics.inc("h2o3_score_flush_entries_total", len(items),
                        arm=arm)
        outs: List[List[tuple]] = [[] for _ in items]
        for w in done:
            if w:
                outs[w[0]].append(w[1:])
        from h2o3_tpu.core.sharded_frame import device_int32

        K = self._out_k()
        tail = () if K == 1 else (K,)
        sharding = self._cl.row_sharding()
        with tracing.span("join", pieces=n_disp):
            margins = []
            for (sf, n), got in zip(items, outs):
                # each window gives the rows it covers; the entry's last
                # gives its whole bucket, whose rows past n the mask zeroes
                lens = tuple(v for _o, v, _b in got[:-1]) + \
                    tuple(b for _o, _v, b in got[-1:])
                fn = _lay_out_fn(lens, sf.padded_rows, tail, sharding)
                margins.append(fn(device_int32(n, self._cl.mesh),
                                  *(o for o, _v, _b in got)))
        return margins, n_disp

    def _lift_entry_margins(self, mg, n: int, padded_rows: int):
        """Pad one entry's exact (n, …) device margins out to its frame's
        padded row count and reshard over the named rows axis (the single
        gather of the serving path — device-to-device, never through the
        coordinator host). Pad rows are exactly 0.0, like
        _raw_for_slice's pad — so the downstream margin→raw→frame math is
        byte-identical between the sharded and host paths."""
        import jax.numpy as jnp

        if padded_rows > n:
            pad = ((0, padded_rows - n),) + ((0, 0),) * (mg.ndim - 1)
            mg = jnp.pad(mg, pad)
        return self._cl.reshard_rows(mg)

    # -- fused explainability (leaf walks) ---------------------------------
    def leaf_matrix(self, adapted, n: int) -> np.ndarray:
        """(n, T) int32 leaf node ids through the fused bucketed bin+leaf
        programs — bitwise-identical to ``spec.bin_columns(adapted)`` +
        ``forest.leaf_index(binned)`` (shared binning/walk cores), but
        compiled once per row bucket instead of once per request shape.
        Leaf assignment, staged probabilities and RuleFit-style path
        consumers ride the same compiled-program discipline as serving
        (recorded PR-2 follow-up). Sharded-eligible frames pack from
        addressable shards; others take the host-packed fallback."""
        import jax
        import jax.numpy as jnp

        if n <= 0:
            return np.zeros((0, self.forest.n_trees), np.int32)
        maxb = self.buckets[-1]
        outs: List[Any] = []
        sf = self._sharded_view(adapted)
        if sf is None and jax.process_count() > 1:
            # ineligible frame on a multi-process cloud: the host-gather
            # fallback below would pull non-addressable columns. Keep the
            # eager device-side pass (the pre-fused path) — it runs in
            # lockstep inside the mirrored op, like predict_batch's
            # generic fallback, and is the bitwise reference anyway.
            binned = self.spec.bin_columns(adapted)
            leaves = self.forest.leaf_index(binned)
            if not getattr(leaves, "is_fully_addressable", True):
                from jax.experimental import multihost_utils

                leaves = multihost_utils.process_allgather(leaves,
                                                           tiled=True)
            return np.asarray(leaves)[:n]
        from h2o3_tpu.memory import stream

        # leaf walks stream T int32 lanes per row instead of K margins
        leaf_row_bytes = 4.0 * (2 * max(len(self.spec.names), 1)
                                + self.forest.n_trees)
        if sf is not None:
            def window(pos: int, m: int):
                bucket = self._bucket_for(m)
                Xd = sf.pack_features(pos, n, bucket)
                call_args = (Xd, self._edges, self._is_cat) + \
                    tuple(self._arrays)
                exe = self._executable_for(bucket, False, call_args,
                                           sharded=True, kind="leaf")
                with tracing.span("dispatch", bucket=bucket, rows=m,
                                  path="leaf_sharded"):
                    out = exe(*call_args)
                self._note_dispatch("leaf_sharded")
                return out[:m]

            outs = stream.run_windows(
                "explain", n, window, maxb, row_bytes=leaf_row_bytes,
                window_sizer=self._window_snap)
            from h2o3_tpu.core import sharded_frame

            sharded_frame.note_packed(n)
        else:
            X = self._features(adapted, n)
            sharding = self._cl.row_sharding()

            def window(pos: int, m: int):
                bucket = self._bucket_for(m)
                buf = np.zeros((bucket, X.shape[1]), np.float32)
                buf[:m] = X[pos: pos + m]
                xd = jax.device_put(buf, sharding)
                call_args = (xd, self._edges, self._is_cat) + \
                    tuple(self._arrays)
                exe = self._executable_for(bucket, False, call_args,
                                           kind="leaf")
                with tracing.span("dispatch", bucket=bucket, rows=m,
                                  path="leaf_host"):
                    out = exe(*call_args)
                self._note_dispatch("leaf_host")
                return out[:m]

            outs = stream.run_windows(
                "explain", n, window, maxb, row_bytes=leaf_row_bytes,
                window_sizer=self._window_snap)
        cat = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
        if not getattr(cat, "is_fully_addressable", True):
            # multi-process cloud: every process reaches this inside its
            # mirrored op (REST turn / follower replay), so the allgather
            # is in lockstep
            from jax.experimental import multihost_utils

            cat = multihost_utils.process_allgather(cat, tiled=True)
        return np.asarray(cat)[:n]

    @property
    def traversal_compiles(self) -> int:
        return len(self._traced)

    # -- request-level API -------------------------------------------------
    def _raw_for_slice(self, margin: np.ndarray, n: int,
                       local: bool = False):
        """Pad an exact (n,)/(n, K) margin slice back out to the cluster's
        padded row count and lift to a row-sharded device array, then run
        the model's margin→raw post-processing. Pad rows carry zeros; they
        are weight-masked out of metrics and sliced off of frames, exactly
        like the generic path's NA-binned pad rows. `local=True` keeps the
        identical padded shape but stays on this process's devices (no
        cluster `put_rows` — that is a global-mesh materialization)."""
        import jax.numpy as jnp

        padded = self._cl.pad_rows(n)
        buf = np.zeros((padded,) + margin.shape[1:], np.float32)
        buf[:n] = margin
        f = buf if local else self._cl.put_rows(buf)
        return self.model._margin_to_raw(jnp.asarray(f))

    def _assemble_result(self, frame, raw, n: int, dest, with_metrics: bool,
                         path: str):
        """(prediction frame installed under `dest`, metrics or None) of one
        entry. Result assembly is where a request first blocks on the
        device: whichever of the two spans first reads a host value waits
        out the traversal — ``fetch`` builds and installs the frame,
        ``metrics`` reads the metrics' host values. No sync is ADDED: these
        calls block with or without tracing."""
        with tracing.span("fetch", rows=n, path=path):
            pred = self.model._raw_to_frame(raw, n, key=dest)
            pred.install()
        if not with_metrics:
            return pred, None
        with tracing.span("metrics", rows=n, path=path):
            return pred, self.model._make_metrics(frame, raw)

    def _adapt_view(self, frame, n: int, local_mp: bool):
        """(adapted frame, its ShardedFrame view or None) of one entry on
        the staged path: span ``adapt``, then span ``view``."""
        with tracing.span("adapt", rows=n):
            adapted = self.model.adapt_test(frame)
        if local_mp:
            return adapted, None
        with tracing.span("view", rows=n, of="shards"):
            return adapted, self._sharded_view(adapted)

    def predict_batch(self, entries: List[Tuple[Any, Optional[str], bool]],
                      local_only: bool = False):
        """Score a coalesced batch: entries = [(frame, dest_key,
        with_metrics)]. Returns [(prediction_frame, metrics_or_None)] in
        entry order; prediction frames are installed under dest_key.

        Default path (sharded data plane, single- AND multi-process):
        per entry, ShardedFrame packs the feature matrix from this
        process's addressable row shards, margins run under shard_map over
        the named 'rows' axis, and one device-side reshard assembles the
        prediction frame — no column ever stages on the coordinator host.
        On a multi-process cloud every process executes the identical SPMD
        program sequence inside the mirrored op (followers replay), so the
        fused path no longer falls back to the generic predict there.
        Entries the view cannot hold (host-resident columns, ragged
        layouts, plane off) take the legacy host-packed dispatch —
        coalesced into one bucketed program — or, multi-process, the
        generic predict path.

        Coalesced dispatch: ALL sharded-eligible entries of a flush run
        through ONE window loop under one memory plan, each entry in
        windows of its own rows (_margins_sharded_batch): a window is the
        entry's packing program and the fused program, back to back, and
        no device op between two windows depends on the combination of row
        counts, so a coalesced flush compiles nothing new. Dispatch counts
        land on /3/ScoringMetrics (``dispatches``) and
        ``h2o3_score_dispatches_total``.

        `local_only=True` is degraded-cloud serving: the followers are
        dead or stale, so no cross-process program may run. The fused
        host-packed path serves from this process alone — local-device
        dispatch, never the global mesh (the sharded path IS a mesh
        program, so it is skipped) — when every column is addressable
        here; non-addressable shards raise ShardUnavailableError (scoring
        them NEEDS the dead peer). That raise is the exceptional path:
        coordinator-addressable sharded frames serve."""
        import jax

        t0 = time.perf_counter()
        local_mp = local_only and jax.process_count() > 1
        if local_mp:
            from h2o3_tpu.core.failure import ShardUnavailableError

            for frame, _, _ in entries:
                for nm in frame.names:
                    data = frame.col(nm).data
                    if not getattr(data, "is_fully_addressable", True):
                        raise ShardUnavailableError(
                            f"cloud degraded and frame {frame.key} has "
                            f"non-coordinator shards (column {nm!r})",
                            owners=_shard_owners(data))
        mp = jax.process_count() > 1
        results: List[Any] = [None] * len(entries)
        host_entries = []          # (idx, frame, adapted, n, dest, wm)
        sharded_entries = []       # (idx, frame, n, dest, wm, sf)
        pipe_entries = []          # (idx, frame, n, dest, wm, capture)
        n_dispatches = 0
        for i, (frame, dest, with_metrics) in enumerate(entries):
            n = frame.nrows
            # pipeline splice FIRST: capture must see the frame BEFORE
            # adapt_test touches column data (a lazy-column fault is an
            # observation point and would flush the pending feature DAG)
            if not mp and not local_only:
                from h2o3_tpu import pipeline

                if pipeline.enabled():
                    try:
                        with tracing.span("view", rows=n, of="pipeline"):
                            cap = pipeline.try_capture(self, frame)
                    except Exception:   # noqa: BLE001 — staged is the
                        cap = None      # contract for anything capture
                    if cap is not None:  # cannot hold
                        pipe_entries.append((i, frame, n, dest,
                                             with_metrics, cap))
                        continue
            adapted, sf = self._adapt_view(frame, n, local_mp)
            if sf is not None:
                sharded_entries.append((i, frame, n, dest, with_metrics,
                                        sf))
            elif mp and not local_only:
                # ineligible entry on a multi-process cloud: the generic
                # path (device-side binning + traversal) keeps the program
                # sequence mirrored without host packing. Reuse the one
                # adaptation above — predict()/model_performance() would
                # each re-adapt the frame (2-3x column transfers per
                # request, on every process)
                raw = self.model._predict_raw(adapted)
                pred = self.model._raw_to_frame(raw, n, key=dest)
                pred.install()
                mm = self.model._make_metrics(frame, raw) if with_metrics \
                    else None
                results[i] = (pred, mm)
            else:
                host_entries.append((i, frame, adapted, n, dest,
                                     with_metrics))
        if pipe_entries:
            from h2o3_tpu import pipeline
            from h2o3_tpu.core import sharded_frame

            for i, frame, n, dest, with_metrics, cap in pipe_entries:
                # munge→score as ONE program per bucket: the captured
                # feature DAG and the forest core dispatch together; no
                # engineered Column ever materializes
                try:
                    mg, nd = pipeline.execute_margins(self, cap)
                except Exception:   # noqa: BLE001 — abandon to staged
                    pipeline.note_fallback(cap)
                    adapted, sf = self._adapt_view(frame, n, local_mp)
                    if sf is not None:
                        sharded_entries.append((i, frame, n, dest,
                                                with_metrics, sf))
                    else:
                        host_entries.append((i, frame, adapted, n, dest,
                                             with_metrics))
                    continue
                n_dispatches += nd
                sharded_frame.note_packed(n)
                with tracing.span("lift", rows=n, path="pipeline"):
                    raw = self.model._margin_to_raw(
                        self._lift_entry_margins(mg, n, cap.padded))
                results[i] = self._assemble_result(frame, raw, n, dest,
                                                   with_metrics, "pipeline")
        if sharded_entries:
            from h2o3_tpu.core import sharded_frame

            margins, nd = self._margins_sharded_batch(
                [(sf, n) for _i, _f, n, _d, _w, sf in sharded_entries])
            n_dispatches += nd
            for (i, frame, n, dest, with_metrics, _sf), mg in zip(
                    sharded_entries, margins):
                # the entry's margins, already laid out as its frame's
                # rows, to raw: eager device ops the host only enqueues
                with tracing.span("lift", rows=n, path="sharded"):
                    raw = self.model._margin_to_raw(mg)
                sharded_frame.note_packed(n)
                results[i] = self._assemble_result(frame, raw, n, dest,
                                                   with_metrics, "sharded")
        if host_entries:
            X = np.concatenate([self._features(a, n)
                                for _, _, a, n, _, _ in host_entries])
            # the host path coalesces into one margin dispatch per bucket
            # chunk of the concatenated rows (the pre-PR-7 batching);
            # _margin_x reports what actually ran
            host_disp: list = []
            margins = self._margin_x(X, local=local_mp,
                                     dispatched=host_disp)
            n_dispatches += len(host_disp)
            off = 0
            for i, frame, _a, n, dest, with_metrics in host_entries:
                raw = self._raw_for_slice(margins[off: off + n], n,
                                          local=local_mp)
                off += n
                results[i] = self._assemble_result(frame, raw, n, dest,
                                                   with_metrics, "host")
        total_rows = sum(frame.nrows for frame, _, _ in entries)
        ms = (time.perf_counter() - t0) * 1000
        self.stats.record_batch(len(entries), total_rows, ms,
                                dispatches=n_dispatches)
        from h2o3_tpu.obs import metrics as obs_metrics
        from h2o3_tpu.utils import timeline

        obs_metrics.observe("h2o3_score_flush_requests",
                            float(len(entries)))
        timeline.record("scoring", str(self.model.key), ms=ms,
                        requests=len(entries), rows=total_rows,
                        dispatches=n_dispatches,
                        compiles=self.traversal_compiles)
        return results

    def predict(self, frame, key: Optional[str] = None):
        """Single-request convenience (no micro-batching, no oplog)."""
        return self.predict_batch([(frame, key, False)])[0][0]


# ---------------------------------------------------------------------------
# session registry (bounded; a retrain under the same key gets a fresh
# session because the CompressedForest identity changes)
# ---------------------------------------------------------------------------

_REG_LOCK = threading.Lock()
_REGISTRY: "collections.OrderedDict[tuple, ScoringSession]" = \
    collections.OrderedDict()
_REGISTRY_CAP = 16


def session_for(model) -> ScoringSession:
    key = (str(model.key), id(model.forest))
    with _REG_LOCK:
        sess = _REGISTRY.get(key)
        if sess is not None:
            _REGISTRY.move_to_end(key)
            return sess
    sess = ScoringSession(model)
    with _REG_LOCK:
        cur = _REGISTRY.setdefault(key, sess)
        _REGISTRY.move_to_end(key)
        while len(_REGISTRY) > _REGISTRY_CAP:
            _REGISTRY.popitem(last=False)
        return cur


def purge(model_key: Optional[str] = None) -> None:
    """Drop sessions for a deleted model (all sessions when key is None)."""
    with _REG_LOCK:
        if model_key is None:
            _REGISTRY.clear()
            return
        for k in [k for k in _REGISTRY if k[0] == str(model_key)]:
            del _REGISTRY[k]


def metrics_snapshot() -> List[Dict[str, Any]]:
    with _REG_LOCK:
        items = [(k[0], s) for k, s in _REGISTRY.items()]
    out = []
    for mk, sess in items:
        entry = {"model": mk, "buckets": list(sess.buckets),
                 "traversal_compiles": sess.traversal_compiles,
                 "fused_compiles": sess.fused_compiles,
                 "compile_cache_hits": sess.cache_hits}
        entry.update(sess.stats.snapshot())
        out.append(entry)
    return out


# ---------------------------------------------------------------------------
# micro-batcher
# ---------------------------------------------------------------------------

class _Pending:
    __slots__ = ("frame", "dest", "with_metrics", "event", "pred", "mm",
                 "error", "promoted", "trace_ctx", "enq_ms")

    def __init__(self, frame, dest, with_metrics):
        self.frame = frame
        self.dest = dest
        self.with_metrics = with_metrics
        self.event = threading.Event()
        self.pred = None
        self.mm = None
        self.error: Optional[BaseException] = None
        self.promoted = False      # woken to take over flush leadership
        # submitter's trace context + enqueue wall time: the flush leader
        # (a different thread) records each request's queue-wait span into
        # ITS trace, and adopts the lead context for the batch phases
        self.trace_ctx = tracing.context()
        self.enq_ms = tracing.now_ms()


def execute_batch(model, entries: List[Tuple[Any, Optional[str], bool]],
                  local_only: bool = False):
    """Run one coalesced batch (shared by the coordinator's flush and the
    follower's oplog replay, so both sides execute the identical device
    program sequence). `local_only` is the degraded-cloud serving mode:
    no cross-process program, coordinator-addressable data only."""
    return session_for(model).predict_batch(entries, local_only=local_only)


class ScoreBatcher:
    """Coalesces concurrent scoring requests per model key.

    The first request for a model becomes the flush leader: it sleeps the
    batch window, drains everything queued for that model, broadcasts ONE
    'score_batch' oplog op, and dispatches the whole batch inside the
    op's execution turn. Followers of the request (other handler threads)
    block on their entry's event and get their exact slice back. Per-model
    queues mean requests against different models proceed independently."""

    def __init__(self):
        self._lock = threading.Lock()
        self._queues: Dict[str, List[_Pending]] = {}
        self._leaders: set = set()

    def submit(self, model, frame, dest: Optional[str] = None,
               with_metrics: bool = False, timeout_s: float = 600.0):
        mk = str(model.key)
        ent = _Pending(frame, dest, with_metrics)
        with self._lock:
            self._queues.setdefault(mk, []).append(ent)
            lead = mk not in self._leaders
            if lead:
                self._leaders.add(mk)
        if lead:
            self._lead(model, mk)
        else:
            if not ent.event.wait(timeout=timeout_s):
                # withdraw BEFORE erroring: a still-queued entry must not
                # be scored later (its client already got the failure) —
                # if it is mid-flush, give that dispatch a grace period
                with self._lock:
                    q = self._queues.get(mk)
                    if q and ent in q:
                        q.remove(ent)
                        if ent.promoted:
                            # leadership was handed to us in the same
                            # instant we gave up — pass it on, don't let
                            # the queue stall behind a departed leader
                            if q:
                                q[0].promoted = True
                                q[0].event.set()
                            else:
                                self._queues.pop(mk, None)
                                self._leaders.discard(mk)
                        raise TimeoutError(
                            f"scoring batch for model {mk!r} did not "
                            f"flush within {timeout_s}s")
                if not ent.event.wait(timeout=60.0):
                    raise TimeoutError(
                        f"scoring dispatch for model {mk!r} wedged "
                        f"mid-batch")
            if ent.promoted and not (ent.pred or ent.error):
                # the previous leader finished its batch with us still
                # queued and handed leadership over: our flush (which
                # includes our own entry) runs on THIS thread
                self._lead(model, mk)
        if ent.error is not None:
            raise ent.error
        return ent.pred, ent.mm

    def _lead(self, model, mk: str) -> None:
        """Flush ONE batch (window sleep → drain → dispatch), then either
        release leadership or hand it to the first still-queued waiter —
        the leader's own request is never delayed past its batch, even
        under a sustained request stream."""
        try:
            w = _window_s()
            if w > 0:
                time.sleep(w)
            with self._lock:
                batch = self._queues.get(mk) or []
                self._queues[mk] = []
            if batch:
                self._flush(model, batch)
            with self._lock:
                rest = self._queues.get(mk)
                if rest:
                    # leadership stays marked; the promoted waiter's
                    # thread continues the flush loop
                    rest[0].promoted = True
                    rest[0].event.set()
                    return
                self._queues.pop(mk, None)
                self._leaders.discard(mk)
        except BaseException as ex:   # noqa: BLE001 — never strand waiters
            with self._lock:
                stranded = self._queues.pop(mk, [])
                self._leaders.discard(mk)
            for e in stranded:
                if e.error is None and not e.event.is_set():
                    e.error = ex
                    e.event.set()
            raise

    @staticmethod
    def _flush(model, batch: List[_Pending]) -> None:
        from h2o3_tpu.parallel import oplog, retry, supervisor

        # queue-wait: submit -> flush start, one span per request in that
        # request's OWN trace; the batch's shared phases (publish, then
        # span ``flush`` over adapt, pack, dispatch, fetch, metrics) run
        # under the lead (oldest) context
        now_ms = tracing.now_ms()
        for e in batch:
            tracing.record_span("queue_wait", e.trace_ctx, e.enq_ms, now_ms,
                                batched_with=len(batch) - 1)
        lead_ctx = next((e.trace_ctx for e in batch if e.trace_ctx), None)
        try:
            # broadcast ONE op for the whole batch; followers replay it
            # once. Existence/compat validation already happened
            # pre-broadcast in the REST handler, so coordinator and
            # follower fail symmetrically. The broadcast sits INSIDE the
            # try: a KV failure must error the waiters, not strand them.
            # A transiently-lost publish is retried with backoff (publish
            # rolled its sequence slot back, so the re-claim is gapless);
            # on a DEGRADED/FAILED cloud scoring skips the broadcast and
            # serves coordinator-locally — the one surface that stays up.
            with tracing.activate(lead_ctx):
                local_only = (oplog.active()
                              and supervisor.state() != supervisor.HEALTHY)
                op_seq = None
                if not local_only:
                    from h2o3_tpu.core.failure import CloudUnhealthyError

                    try:
                        op_seq = retry.retry_call(
                            oplog.broadcast, "score_batch", {
                                "model": str(model.key),
                                "requests": [{"frame": str(e.frame.key),
                                              "destination_frame": e.dest,
                                              "with_metrics":
                                              bool(e.with_metrics)}
                                             for e in batch]},
                            retry_on=(oplog.OplogPublishError,),
                            describe="score_batch broadcast")
                    except CloudUnhealthyError:
                        # the cloud degraded between the state snapshot and
                        # the broadcast's own fail-fast check: scoring is
                        # the surface that keeps serving — fall back to
                        # local
                        local_only = True
                if local_only:
                    # local serving installs prediction frames only in the
                    # COORDINATOR's DKV (no oplog record): follower key
                    # state is now behind, so the degraded verdict must
                    # never auto-recover — only a cloud restart re-syncs
                    supervisor.degrade(
                        "coordinator-local scoring served while degraded: "
                        "follower DKV state is behind; restart the cloud "
                        "to re-sync", hold_s=float("inf"))
                with tracing.span("flush", requests=len(batch)) as fl, \
                        oplog.turn(op_seq):
                    results = execute_batch(
                        model, [(e.frame, e.dest, e.with_metrics)
                                for e in batch],
                        local_only=local_only)
            # the requests coalesced behind the lead waited out the same
            # interval: each gets it in its OWN trace, naming the lead's
            if fl:
                for e in batch:
                    if e.trace_ctx is not lead_ctx:
                        tracing.record_span(
                            "flush", e.trace_ctx, fl.span["start_ms"],
                            fl.span["end_ms"], lead=lead_ctx["trace_id"],
                            requests=len(batch))
            for e, (pred, mm) in zip(batch, results):
                e.pred, e.mm = pred, mm
        except BaseException as ex:   # noqa: BLE001 — propagate per-request
            for e in batch:
                e.error = ex
        finally:
            for e in batch:
                e.event.set()


BATCHER = ScoreBatcher()


def score_request(model, frame, dest: Optional[str] = None,
                  with_metrics: bool = False):
    """Entry point for the REST layer: admission-controlled, coalescing,
    bucketed, oplog-mirrored scoring of one request. Returns
    (prediction_frame, metrics_or_None). Over the per-model concurrency
    limit requests queue (bounded); overflow raises AdmissionRejected,
    which the REST layer maps to 429/503 + Retry-After — heavy traffic
    degrades by queueing, not collapse.

    Every served request's latency feeds the per-model admission ring:
    the SLO-adaptive controller (``H2O_TPU_SCORE_SLO_MS``) derives the
    inflight limit from the observed p99 against the target, and the
    Retry-After hints from the observed drain rate."""
    from h2o3_tpu import admission
    from h2o3_tpu.obs import metrics as obs_metrics

    mk = str(model.key)
    t0 = time.perf_counter()
    with admission.CONTROLLER.slot(mk):
        t1 = time.perf_counter()
        out = BATCHER.submit(model, frame, dest, with_metrics)
        admission.CONTROLLER.note_latency(
            mk, (time.perf_counter() - t1) * 1000.0)
    obs_metrics.observe("h2o3_score_request_seconds",
                        time.perf_counter() - t0, model=mk)
    return out

"""Sharded data plane: per-process feature packing over addressable shards.

Reference: H2O-3's entire engine is "map/reduce over chunks that live where
they are" (water/fvec/Chunk.java homing + water/MRTask.java local maps) —
no node ever pulls another node's chunks to build a task's input. The
TPU-native analog (ROADMAP open item 1, the recorded blocker of PRs 2/3/4):
columns are row-sharded jax.Arrays over the mesh's named ``rows`` axis, so
"chunk locality" is the ``NamedSharding`` rule — and every input-building
step (serving feature packing, tree-training bin matrices) must consume
those shards WHERE THEY ARE instead of round-tripping whole columns
through the coordinator host.

:class:`ShardedFrame` is that contract as a view over ``core/frame.Frame``:

- **named row axis** — ``ROW_AXIS`` ("rows"), the mesh axis every column's
  ``NamedSharding`` partitions; the same axis the fused scorers
  ``shard_map`` over (compressed.py ``_fused_score_sharded_fn``, routed
  through ``compat.shard_map``).
- **pack_features** — the serving fast path's (bucket, F) float32 feature
  matrix built by ONE compiled program whose output keeps the row
  sharding: each process materializes only its addressable shards
  (``jit`` + ``out_shardings``; the slice/cast/mask is elementwise over
  rows, so XLA keeps per-shard work local). Bitwise-identical to the
  host-packed path's matrix: same casts, same zero pad.
- **pack_binned** — the tree-training input build: the (N, F) integer bin
  matrix fused into one program with a ``P('rows', None)`` output, so
  training input pipelines never stage full columns on the coordinator
  (previously: eager per-column ops + a re-homing device_put).

Per-process counters make the no-gather property OBSERVABLE
(``GET /3/ScoringMetrics`` → ``data_plane``): ``packed_rows`` counts rows
packed shard-locally; ``gathered_rows`` counts rows whose columns WERE
pulled to this process's host inside the fused scoring / tree input paths
(the degraded-serving and ragged-layout fallbacks). tests/test_consistency
asserts ``gathered_rows`` stays 0 on the sharded path.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import List, Optional, Sequence

import numpy as np

ROW_AXIS = "rows"

# -- per-process data-plane counters ----------------------------------------

_LOCK = threading.Lock()
_PACKED = 0
_GATHERED = 0
_SORTED = 0


def note_packed(n: int) -> None:
    """Record `n` rows whose task input was built from addressable shards
    in place (no host round-trip)."""
    global _PACKED
    with _LOCK:
        _PACKED += int(n)


def note_gathered(n: int) -> None:
    """Record `n` rows whose columns were fetched to this process's host
    inside the fused scoring / tree input path (the exceptional path)."""
    global _GATHERED
    with _LOCK:
        _GATHERED += int(n)


def note_sorted(n: int) -> None:
    """Record `n` rows ordered by a device sort whose permutation never
    crossed to the host (ops/sort.py device paths — the lazy-session PR's
    'sort stops being the host-keyed path' observable)."""
    global _SORTED
    with _LOCK:
        _SORTED += int(n)


def counters() -> dict:
    with _LOCK:
        return {"packed_rows": _PACKED, "gathered_rows": _GATHERED,
                "device_sorted_rows": _SORTED}


def reset_counters() -> None:
    global _PACKED, _GATHERED, _SORTED
    with _LOCK:
        _PACKED = 0
        _GATHERED = 0
        _SORTED = 0


def enabled() -> bool:
    """Master switch for the sharded data plane (H2O_TPU_SHARDED_PLANE,
    default on). Off = the legacy host-packed / eager paths, kept for
    A/B bitwise verification and emergency rollback."""
    return os.environ.get("H2O_TPU_SHARDED_PLANE", "1").lower() not in (
        "0", "false", "off")


def shard_geometry(cl, padded: int):
    """(shard_rows, addressable shard indices) for a padded row count.
    The authority is the row sharding's OWN index map (what put_rows
    materializes), never process_index — the chunked sharded ingest
    (ingest/chunked.py) uses it to land each byte-range chunk's rows
    directly in their owning shard buffers."""
    shard_rows = padded // max(cl.row_shards, 1)
    sh = cl.row_sharding()
    idx_map = sh.addressable_devices_indices_map((padded,))
    return shard_rows, {(sl[0].start or 0) // shard_rows
                        for sl in idx_map.values()}


# -- compiled packers (cached per geometry, not per request) ----------------

@functools.lru_cache(maxsize=64)
def _pack_features_fn(bucket: int, padded: int, dtypes: tuple, mesh):
    """(pos, n, *cols) -> (bucket, F) float32, row-sharded.

    Matches ScoringSession._features + its zero pad bitwise: values pass
    through for logical rows [pos, min(pos+bucket, n)) — numerics as-is
    (NaN = NA, bf16 upcast exactly as numpy's), categorical codes cast to
    float (NA_CAT stays negative) — and every other row is exactly 0.0.
    pos/n are traced scalars, so one compile covers every request against
    this (bucket, layout). `padded`/`dtypes` are cache-key-only: they pin
    the jit wrapper to one column layout so its trace cache never aliases
    across layouts."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    def pack(pos, n, *cols):
        idx = pos + jnp.arange(bucket, dtype=jnp.int32)
        valid = idx < n
        parts = []
        for c in cols:
            x = c.astype(jnp.float32)
            # pad THEN slice: a tail chunk's [pos, pos+bucket) window may
            # overrun the padded column, and dynamic_slice would clamp the
            # start (silently shifting rows); the zero tail keeps the
            # window in bounds and is masked off below anyway
            x = jnp.pad(x, (0, bucket))
            parts.append(jax.lax.dynamic_slice_in_dim(x, pos, bucket))
        X = jnp.stack(parts, axis=-1)
        return jnp.where(valid[:, None], X, jnp.float32(0))

    return jax.jit(pack, out_shardings=NamedSharding(mesh, P(ROW_AXIS, None)))


def edges_below(edges, x):
    """How many of a feature's ascending edges lie below x, as int32: the
    bin of x (searchsorted side='left'; the +inf pad lanes of a padded edge
    row never count), counted by compare-and-sum over the edge axis, one
    fused reduce of N x E compares and no (N, E) intermediate. The default
    binary search is log2 E per-row gathers: on a v5e at 16M rows a column
    takes 1.06 / 1.41 / 1.20 / 1.80 s at 100 / 256 / 1,024 / 4,096 edges,
    this 0.0028 / 0.0049 / 0.027 / 0.106 s (PERF.md section 6, PR 28);
    the lines would cross near 90,000 edges, beyond any nbins in use."""
    import jax.numpy as jnp

    return jnp.searchsorted(edges, x, side="left",
                            method="compare_all").astype(jnp.int32)


@functools.lru_cache(maxsize=64)
def _pack_binned_fn(padded: int, dtypes: tuple, nbins: tuple, is_cat: tuple,
                    out_dtype: str, mesh):
    """(edges, *cols) -> (padded, F) integer bin matrix, row-sharded.

    The fused replacement for BinSpec.bin_columns' eager per-column loop:
    same bin math (searchsorted side='left' over the real edges — the +inf
    pad lanes never count — NA/out-of-range to the per-feature NA bin),
    one XLA program, output sharding P('rows', None) so each process bins
    only its addressable row shards."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    dt = getattr(jnp, out_dtype)

    def pack(edges, *cols):
        parts = []
        for i, c in enumerate(cols):
            na_bin = int(nbins[i]) - 1
            if is_cat[i]:
                codes = c.astype(jnp.int32)
                b = jnp.where((codes < 0) | (codes >= na_bin), na_bin, codes)
            else:
                x = c
                b = edges_below(edges[i], x)
                b = jnp.where(jnp.isnan(x), na_bin, b)
            parts.append(b.astype(dt))
        return jnp.stack(parts, axis=-1)

    return jax.jit(pack, out_shardings=NamedSharding(mesh, P(ROW_AXIS, None)))


@functools.lru_cache(maxsize=64)
def _pack_binned_window_fn(win: int, padded: int, dtypes: tuple,
                           nbins: tuple, is_cat: tuple, out_dtype: str,
                           mesh):
    """(pos, edges, *cols) -> (win, F) bin matrix for rows
    [pos, pos+win) — the chunk-streamed twin of _pack_binned_fn for
    frames whose full (padded, F) bin matrix exceeds the memory
    planner's budget. Same bin math on pad→dynamic-sliced column
    windows (identical values per covered row → bitwise-identical bins);
    the overrun lanes of a tail window are trimmed by the caller. Full
    columns stay in place as args — only the temporaries and the output
    shrink to the window, which is where the working set lives."""
    import jax
    import jax.numpy as jnp

    dt = getattr(jnp, out_dtype)

    def pack(pos, edges, *cols):
        parts = []
        for i, c in enumerate(cols):
            x = jax.lax.dynamic_slice_in_dim(jnp.pad(c, (0, win)), pos, win)
            na_bin = int(nbins[i]) - 1
            if is_cat[i]:
                codes = x.astype(jnp.int32)
                b = jnp.where((codes < 0) | (codes >= na_bin), na_bin, codes)
            else:
                b = edges_below(edges[i], x)
                b = jnp.where(jnp.isnan(x), na_bin, b)
            parts.append(b.astype(dt))
        return jnp.stack(parts, axis=-1)

    return jax.jit(pack)


# packer executables, AOT-compiled through the compile ledger (family
# "pack") so the data plane's compiles land on /3/Runtime like every
# other program. Keyed by geometry + the concrete input shardings: a
# frame with a different layout gets its own recorded compile instead of
# a silent uncounted jit trace.
_EXE_LOCK = threading.Lock()
_EXE_CACHE: dict = {}
_EXE_CAP = 64


_EXE_MISS = object()


def _packer_exe(key: tuple, jfn, call_args, program: str,
                family: str = "pack", rows: int = 0):
    """Ledger-recorded AOT executable for one packer geometry (or None
    when AOT lowering/compilation itself fails on this layout/backend —
    cached so the failure is paid once and callers permanently use the
    jit twin, exactly the pre-ledger behavior). Lowered from the
    CONCRETE first-call args (jit-identical program, exact input
    shardings).

    Hot-path cost discipline: the warm lookup is a lock-free dict get
    (GIL-atomic); _EXE_LOCK is held only across the miss path, where the
    double-checked re-read makes concurrent first-touch threads pay ONE
    compile (and land one ledger row) instead of racing duplicates."""
    exe = _EXE_CACHE.get(key, _EXE_MISS)
    if exe is not _EXE_MISS:
        return exe
    with _EXE_LOCK:
        exe = _EXE_CACHE.get(key, _EXE_MISS)
        if exe is not _EXE_MISS:
            return exe
        try:
            from h2o3_tpu.obs import compiles

            exe = compiles.compile_jit(family, jfn, call_args,
                                       signature=key, program=program)
            if rows > 0:
                from h2o3_tpu.memory import budget as membudget

                membudget.note_compiled(family, int(rows), exe)
        except Exception:   # noqa: BLE001 — AOT unavailable for this
            exe = None      # layout: the jit twin still dispatches
        if len(_EXE_CACHE) >= _EXE_CAP:
            _EXE_CACHE.pop(next(iter(_EXE_CACHE)))
        _EXE_CACHE[key] = exe
    return exe


# device int32 scalars for the packers' `pos` / `n` (and the scoring
# lay-out's `n`), replicated over the mesh where the executables read them,
# keyed by value: a host value handed to an executable is a transfer every
# call (about 200 us each on a v5e's host, PERF.md section 6, PR 37), a
# cached device value none. A flush reads few values (the multiples of a
# bucket, one n a frame), so a bound like _EXE_CACHE's holds them.
_SCALARS: dict = {}
_SCALAR_CAP = 4096
_SCALAR_LOCK = threading.Lock()


def device_int32(v: int, mesh):
    """The int32 scalar `v` as a device array replicated over `mesh`,
    made once a value (lock-free warm read, a lock on the miss)."""
    key = (int(v), mesh)
    d = _SCALARS.get(key)
    if d is not None:
        return d
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    with _SCALAR_LOCK:
        d = _SCALARS.get(key)
        if d is None:
            d = jax.device_put(np.int32(v), NamedSharding(mesh, P()))
            if len(_SCALARS) >= _SCALAR_CAP:
                _SCALARS.pop(next(iter(_SCALARS)))
            _SCALARS[key] = d
    return d


def _sharding_key(arrs) -> tuple:
    # the sharding OBJECTS, not their str(): jax shardings are hashable/
    # eq-comparable, and stringifying one per column per dispatch would
    # tax the data-plane hot path for nothing
    return tuple(getattr(a, "sharding", None) for a in arrs)


class ShardedFrame:
    """Row-sharded data-plane view over a Frame's device columns.

    Build with :meth:`of` (returns None when the view cannot hold: a named
    column is host-resident (strings), layouts disagree, or the plane is
    switched off) — callers fall back to their legacy host/eager path and
    count the rows as ``gathered``."""

    __slots__ = ("frame", "names", "_datas", "_dtypes", "_cl",
                 "padded_rows")

    def __init__(self, frame, names: List[str], datas: list, cl,
                 padded_rows: int):
        self.frame = frame
        self.names = names
        self._datas = datas
        # the packers' cache key: once a view, never once a chunk
        self._dtypes = tuple(str(d.dtype) for d in datas)
        self._cl = cl
        self.padded_rows = padded_rows

    @classmethod
    def of(cls, frame, names: Optional[Sequence[str]] = None
           ) -> Optional["ShardedFrame"]:
        if not enabled():
            return None
        from h2o3_tpu.core.runtime import cluster

        cl = cluster()
        use = list(names) if names is not None else list(frame.names)
        datas, padded = [], None
        for nm in use:
            c = frame.col(nm)
            if c.ctype not in ("real", "int", "enum", "time"):
                return None            # host-resident (string/uuid) column
            d = c.data                 # faults evicted columns back in
            if d is None:
                return None
            if padded is None:
                padded = int(d.shape[0])
            elif int(d.shape[0]) != padded:
                return None            # ragged layout: no shared row axis
            datas.append(d)
        if padded is None or padded % max(cl.row_shards, 1):
            return None
        return cls(frame, use, datas, cl, padded)

    @classmethod
    def for_key(cls, key, names: Optional[Sequence[str]] = None
                ) -> Optional["ShardedFrame"]:
        """DKV-resident variant: resolve `key` through the control plane
        (local store first, replicated payload second) and wrap it."""
        from h2o3_tpu.core.dkv import DKV

        fr = DKV.fetch_remote(key)
        return cls.of(fr, names) if fr is not None else None

    # -- layout -----------------------------------------------------------
    @property
    def row_axis(self) -> str:
        return ROW_AXIS

    @property
    def mesh(self):
        return self._cl.mesh

    def row_sharding(self, ncols: bool = False):
        """The view's NamedSharding: rows over the named axis (optionally
        with an unsharded trailing column axis)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(ROW_AXIS, None) if ncols else P(ROW_AXIS)
        return NamedSharding(self._cl.mesh, spec)

    # -- packers -----------------------------------------------------------
    def pack_features(self, pos: int, n: int, bucket: int):
        """(bucket, F) float32 scoring matrix for logical rows
        [pos, min(pos+bucket, n)), zero elsewhere — built on device from
        the columns' addressable shards; the host never sees a column.
        `pos` and `n` go as cached device scalars (device_int32): once a
        value has been seen, no transfer or eager device op runs before
        the executable."""
        from h2o3_tpu.obs import tracing

        dtypes = self._dtypes
        fn = _pack_features_fn(int(bucket), self.padded_rows, dtypes,
                               self._cl.mesh)
        mesh = self._cl.mesh
        args = (device_int32(pos, mesh), device_int32(n, mesh)) + \
            tuple(self._datas)
        exe = _packer_exe(
            ("features", int(bucket), self.padded_rows, dtypes,
             self._cl.mesh, _sharding_key(self._datas)),
            fn, args, program="pack_features", rows=int(bucket))
        # host-side dispatch wall time only — the packed matrix stays
        # device-resident and no sync is added (span is inert without an
        # active trace)
        with tracing.span("pack", bucket=int(bucket), rows=int(n),
                          path="sharded"):
            if exe is None:
                return fn(*args)
            try:
                return exe(*args)
            except Exception:   # noqa: BLE001 — AOT layout/placement
                return fn(*args)   # mismatch: the jit twin still fits

    def pack_binned(self, spec):
        """(padded_rows, F) integer bin matrix for tree training, fused
        and row-sharded (see _pack_binned_fn). Counts the frame's logical
        rows as packed."""
        import jax.numpy as jnp

        from h2o3_tpu.obs import tracing

        max_bins = int(spec.nbins.max()) if len(spec.nbins) else 1
        out_dtype = ("uint8" if max_bins <= 256
                     else "int16" if max_bins <= 32767 else "int32")
        dtypes = tuple(str(d.dtype) for d in self._datas)
        nbins = tuple(int(b) for b in spec.nbins)
        is_cat = tuple(bool(c) for c in spec.is_cat)
        fn = _pack_binned_fn(self.padded_rows, dtypes, nbins, is_cat,
                             out_dtype, self._cl.mesh)
        edges = jnp.asarray(spec.padded_edges())
        args = (edges,) + tuple(self._datas)
        exe = _packer_exe(
            ("binned", self.padded_rows, dtypes, nbins, is_cat, out_dtype,
             self._cl.mesh, _sharding_key(self._datas)),
            fn, args, program="pack_binned", family="binning",
            rows=self.padded_rows)
        note_packed(int(self.frame.nrows))

        from h2o3_tpu.memory import stream as mstream

        n_pad = self.padded_rows
        item = int(np.dtype(out_dtype).itemsize)
        # per window row: F float32 column lanes in flight + F output lanes
        row_bytes = float(len(self._datas)) * (4.0 + item)

        def window(pos, m):
            if pos == 0 and m == n_pad:
                # planned-full: the exact single-dispatch program
                if exe is None:
                    return fn(*args)
                try:
                    return exe(*args)
                except Exception as e:   # noqa: BLE001
                    if mstream.is_oom(e):
                        raise           # the ladder owns exhaustion
                    return fn(*args)    # AOT layout mismatch: jit twin
            w = 1 << max(int(m) - 1, 0).bit_length()
            wfn = _pack_binned_window_fn(w, n_pad, dtypes, nbins, is_cat,
                                         out_dtype, self._cl.mesh)
            out = wfn(jnp.int32(pos), *args)
            return out[:m] if m != w else out

        with tracing.span("pack", rows=int(self.frame.nrows),
                          path="binned"):
            pieces = mstream.run_windows("binning", n_pad, window,
                                         max_window=n_pad,
                                         row_bytes=row_bytes)
        return (pieces[0] if len(pieces) == 1
                else jnp.concatenate(pieces, axis=0))

    def __repr__(self) -> str:
        return (f"<ShardedFrame {getattr(self.frame, 'key', '?')} "
                f"{self.padded_rows}x{len(self.names)} axis={ROW_AXIS}>")

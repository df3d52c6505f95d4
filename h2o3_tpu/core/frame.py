"""Columnar store: Frame / Column.

Reference design: Frame -> Vec -> Chunk with 19 compression codecs and
inflate-on-write (water/fvec/Frame.java:64, Vec.java:157, Chunk.java:113,
NewChunk.java:22), ragged ESPC row layout, lazily-computed RollupStats
(water/fvec/RollupStats.java:30).

TPU-native design (SURVEY.md §7):
- One dense device array per column, row-sharded over the mesh 'rows' axis
  (`NamedSharding(P('rows'))`) — chunk homing becomes the sharding rule.
- Static shapes: rows padded to a multiple of (shards * row_align); the pad
  sentinel doubles as the NA sentinel, so masked reductions skip both.
- NA encoding replaces the codec zoo + mask machinery: numeric = NaN,
  categorical/int = -1. XLA's fusion makes narrow-dtype compression moot in
  HBM terms for f32; categoricals are int32 codes with a host-side domain
  (strings NEVER go to device).
- Columns are immutable: Rapids assign becomes copy-on-write version chains
  instead of Chunk inflate-on-write (Chunk.java:427-451).
- RollupStats = one fused jitted reduction, cached on the (immutable) column.
"""

from __future__ import annotations

import itertools
import math
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np

from h2o3_tpu.core.dkv import DKV, Key, Keyed

# Column logical types (water/fvec/Vec.java:160 BAD/UUID/STR/NUM/CAT/TIME)
T_NUM = "real"
T_INT = "int"
T_CAT = "enum"
T_TIME = "time"
T_STR = "string"
T_UUID = "uuid"
T_BAD = "bad"

NA_CAT = np.int32(-1)

# monotonically increasing Column identity tokens (Column.token); CPython's
# GIL makes next() atomic, so no lock is needed
_COLUMN_TOKENS = itertools.count(1)


def code_dtype(n_levels: int):
    """Narrowest signed code dtype that fits the domain plus the -1 NA
    sentinel (SURVEY §7 narrow-dtype design — the replacement for the
    reference's 19-codec chunk zoo, water/fvec/NewChunk.java compress()).
    Ops upcast at their boundaries (binning/DataInfo cast to int32/f32).
    The ONE categorical storage rule — shared by from_numpy and the
    chunked sharded ingest assembly (ingest/chunked.py)."""
    if n_levels <= 126:
        return np.int8
    if n_levels <= 32766:
        return np.int16
    return np.int32


_code_dtype = code_dtype        # historical internal name


def numeric_store_dtype(ctype: str):
    """The ONE numeric storage rule (shared by pad_numeric_host and the
    chunked sharded ingest assembly): T_NUM honors the cluster's bf16
    opt-in; T_TIME/T_INT stay f32."""
    return _numeric_dtype() if ctype == T_NUM else np.dtype(np.float32)


def pad_numeric_host(arr, n: int, padded: int, ctype: str) -> np.ndarray:
    """The one place deciding numeric padded-buffer layout (shared by
    Column.from_numpy and file-backed loaders): dtype per
    numeric_store_dtype; pad tail is NaN."""
    dt = numeric_store_dtype(ctype)
    buf = np.full(padded, np.nan, dt)
    buf[:n] = np.asarray(arr, np.float64).astype(dt)
    return buf


def _numeric_dtype():
    """Device storage dtype for numeric columns: float32 default, bfloat16
    when the cluster opts in (halves HBM per column; compute still runs in
    f32 via the MXU's preferred_element_type / DataInfo's casts)."""
    from h2o3_tpu.core.runtime import cluster

    name = getattr(cluster().args, "numeric_dtype", "float32")
    if name in ("bfloat16", "bf16"):
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(np.float32)


def _cluster():
    from h2o3_tpu.core.runtime import cluster

    return cluster()


class Column:
    """A distributed column (Vec analog, water/fvec/Vec.java:157).

    data: jax.Array (padded_rows,) row-sharded; float32 for real/int/time
    (NaN = NA/pad) or int32 for enum (-1 = NA/pad). For string/uuid columns
    the data lives host-side in `host_data` (object ndarray) and `data` is
    None — TPUs never touch strings (SURVEY.md §7).
    """

    __slots__ = ("_data", "_evicted", "_loader", "_touch", "ctype", "domain",
                 "host_data", "nrows", "_rollups", "_mode", "_chunks",
                 "_token")

    def __init__(self, data, ctype: str, nrows: int,
                 domain: Optional[List[str]] = None,
                 host_data: Optional[np.ndarray] = None):
        self._data = data
        self._evicted = None       # host copy (or loader) while out of HBM
        self._loader = None        # file-backed source (FileVec analog)
        self._touch = 0            # LRU clock (core/cleaner.py)
        self.ctype = ctype
        self.domain = domain
        self.host_data = host_data
        self.nrows = int(nrows)
        self._rollups = None
        self._mode = None
        # minted eagerly: a lazy check-then-set would race under the
        # threaded REST server and hand two threads different tokens
        self._token = next(_COLUMN_TOKENS)

    # -- HBM residency (water/Cleaner.java analog: cold columns swap to
    #    host RAM; access faults them back in) ----------------------------
    @property
    def data(self):
        from h2o3_tpu.core import cleaner

        d = self._data
        while d is None:
            # `_evicted` is either a host buffer (Cleaner swap-out) or a
            # CALLABLE loader (file-backed Vec, water/fvec/FileVec.java
            # analog). The possibly-slow load/decode runs OUTSIDE the swap
            # lock so concurrent fault-ins of other columns don't serialize
            # behind a disk read; the install happens under the lock only
            # if _evicted is still the SAME source we materialized (a
            # racing evict/fault-in cycle retries with the fresh state).
            src = self._evicted
            if src is None:
                d = self._data      # plain data-less column, or raced-in
                break
            buf = src() if callable(src) else src
            with cleaner.SWAP_LOCK:
                if self._data is None and self._evicted is src:
                    self._data = _cluster().put_rows(buf)
                    self._evicted = None
                d = self._data
        self._touch = cleaner.tick()
        # returning the local binding keeps this safe against an evict()
        # landing between the check and the return: the caller's reference
        # pins the device buffer it already obtained
        return d

    @staticmethod
    def file_backed(loader, ctype: str, nrows: int,
                    domain: Optional[List[str]] = None) -> "Column":
        """A column whose device buffer materializes lazily from `loader()`
        (must return the PADDED host buffer) on first data access."""
        c = Column(None, ctype, nrows, domain=domain)
        c._evicted = loader
        c._loader = loader      # evictions revert to the source
        return c

    @data.setter
    def data(self, v):
        from h2o3_tpu.core import cleaner

        # under SWAP_LOCK so a concurrent evict() can't capture the old
        # loader mid-rebind; clearing _loader makes the rebound buffer
        # authoritative (evict falls back to a host copy, not stale disk)
        with cleaner.SWAP_LOCK:
            self._data = v
            self._evicted = None
            self._loader = None

    def evict(self) -> int:
        """Swap the device buffer to host RAM; returns bytes freed. No-op
        for multi-process shardings (remote shards are not addressable
        here) and for host-resident string columns."""
        from h2o3_tpu.core import cleaner

        with cleaner.SWAP_LOCK:
            if self._data is None or \
                    not getattr(self._data, "is_fully_addressable", True):
                return 0
            freed = int(self._data.nbytes)
            # file-backed columns revert to their DISK source — eviction
            # must free host RAM too, not pin a padded copy of the file
            self._evicted = (self._loader if self._loader is not None
                             else np.asarray(self._data))
            self._data = None
            return freed

    @property
    def is_evicted(self) -> bool:
        return self._data is None and self._evicted is not None

    @property
    def device_nbytes(self) -> int:
        return int(self._data.nbytes) if self._data is not None else 0

    # -- constructors -----------------------------------------------------
    @staticmethod
    def from_numpy(arr: np.ndarray, ctype: Optional[str] = None,
                   domain: Optional[List[str]] = None) -> "Column":
        """Build a device column from host data; pads + shards + pins to HBM."""
        import jax
        import jax.numpy as jnp

        cl = _cluster()
        n = len(arr)
        padded = cl.pad_rows(n)

        if ctype is None:
            if arr.dtype.kind in "OUS":
                return Column._from_strings(arr)
            elif arr.dtype.kind in "fiub":
                ctype = T_INT if arr.dtype.kind in "iub" else T_NUM
            elif arr.dtype.kind == "M":
                ctype = T_TIME
            else:
                raise TypeError(f"unsupported dtype {arr.dtype}")

        if ctype == T_CAT:
            a = np.asarray(arr)
            if a.dtype.kind in "OUS":
                dom, codes = _intern_domain(a)
                domain = dom
            else:
                codes = (np.where(np.isnan(a.astype(np.float64)), NA_CAT,
                                  a.astype(np.float64)).astype(np.int32)
                         if a.dtype.kind == "f" else a.astype(np.int32))
            card = len(domain) if domain is not None \
                else int(max(codes.max(initial=0) + 1, 1))
            buf = np.full(padded, NA_CAT, _code_dtype(card))
            buf[:n] = codes
        elif ctype in (T_TIME, T_INT, T_NUM):
            # dtype rules live in pad_numeric_host: T_NUM may opt into bf16;
            # times (epoch-millis precision) and integer keys stay f32
            buf = pad_numeric_host(arr, n, padded, ctype)
        else:
            raise TypeError(f"cannot device-store ctype {ctype}")

        data = cl.put_rows(buf)
        host = None
        if ctype == T_TIME and np.asarray(arr).dtype.kind in "Mi":
            host = np.asarray(arr)  # exact epoch-millis kept host-side
        return Column(data, ctype, n, domain=domain, host_data=host)

    @staticmethod
    def _from_strings(arr: np.ndarray) -> "Column":
        a = np.asarray(arr, dtype=object)
        return Column(None, T_STR, len(a), host_data=a)

    @staticmethod
    def from_device(data, ctype: str, nrows: int,
                    domain: Optional[List[str]] = None) -> "Column":
        return Column(data, ctype, nrows, domain=domain)

    # -- identity ---------------------------------------------------------
    @property
    def token(self) -> int:
        """Process-unique stable identity for this Column. Unlike ``id()``
        it is never reused after GC, so it is safe as a dictionary key
        that may outlive the object (Rapids Session refcounts, fusion
        leaf dedup)."""
        return self._token

    # -- introspection ----------------------------------------------------
    @property
    def is_numeric(self) -> bool:
        return self.ctype in (T_NUM, T_INT)

    @property
    def is_categorical(self) -> bool:
        return self.ctype == T_CAT

    @property
    def is_string(self) -> bool:
        return self.ctype == T_STR

    @property
    def cardinality(self) -> int:
        return len(self.domain) if self.domain else 0

    @property
    def padded_rows(self) -> int:
        return int(self.data.shape[0]) if self.data is not None else len(self.host_data)

    def to_numpy(self) -> np.ndarray:
        """Gather the logical (unpadded) rows back to host. On a
        multi-process cloud the column spans non-addressable devices —
        allgather the shards so any process sees the full column (the
        reference's as_data_frame works from any node: water/Frame fetch
        over RPC; here it rides the jax.distributed transport)."""
        if self.data is None:
            return self.host_data[: self.nrows]
        data = self.data
        if not getattr(data, "is_fully_addressable", True):
            from jax.experimental import multihost_utils

            from h2o3_tpu.parallel import oplog

            if oplog.unmirrored_collective_risk():
                # a REST handler outside its op turn must not enter a
                # collective the follower will never join — fail fast with
                # the actionable error instead of deadlocking the mesh
                raise RuntimeError(
                    "host fetch of a multi-process frame from a REST "
                    "handler requires an oplog-mirrored op (followers "
                    "replay broadcast ops only)")
            data = multihost_utils.process_allgather(data, tiled=True)
        arr = np.asarray(data)[: self.nrows]
        return arr

    def values(self) -> np.ndarray:
        """Decode to user-facing values (enum codes -> labels)."""
        arr = self.to_numpy()
        if self.ctype == T_CAT and self.domain is not None:
            dom = np.asarray(self.domain, dtype=object)
            out = np.empty(len(arr), dtype=object)
            valid = arr >= 0
            out[valid] = dom[arr[valid]]
            out[~valid] = None
            return out
        return arr

    # -- rollups ----------------------------------------------------------
    @property
    def rollups(self):
        """Lazy fused min/max/mean/sigma/naCnt/nzCnt (RollupStats.java:30)."""
        if self._rollups is None:
            from h2o3_tpu.ops.rollups import compute_rollups

            self._rollups = compute_rollups(self)
        return self._rollups

    @property
    def mode(self) -> int:
        """Most frequent level of a categorical column (what mode imputation
        fills with), computed once a column like the rollups."""
        if self._mode is None:
            from h2o3_tpu.ops.rollups import compute_mode

            self._mode = compute_mode(self)
        return self._mode

    def min(self):
        return self.rollups.min

    def max(self):
        return self.rollups.max

    def mean(self):
        return self.rollups.mean

    def sigma(self):
        return self.rollups.sigma

    def na_count(self):
        return self.rollups.na_count

    # -- transforms (copy-on-write) --------------------------------------
    def with_data(self, data, ctype: Optional[str] = None,
                  domain: Optional[List[str]] = None) -> "Column":
        return Column(data, ctype or self.ctype, self.nrows,
                      domain=domain if domain is not None else self.domain)

    def valid_mask(self):
        """Device bool mask of valid (non-NA, non-pad) rows."""
        import jax.numpy as jnp

        if self.ctype == T_CAT:
            return self.data >= 0
        return ~jnp.isnan(self.data)


def _intern_domain(a: np.ndarray) -> Tuple[List[str], np.ndarray]:
    """Global categorical interning (water/parser/Categorical.java): string
    labels -> dense int codes, domain sorted lexicographically (H2O sorts
    domains, water/parser/ParseDataset.java:518 GatherCategoricalDomainsTask)."""
    mask_na = np.array([x is None or (isinstance(x, float) and math.isnan(x)) or x == "" for x in a])
    vals = np.asarray([("" if m else str(x)) for x, m in zip(a, mask_na)])
    dom = sorted(set(vals[~mask_na].tolist()))
    lookup = {v: i for i, v in enumerate(dom)}
    codes = np.array([NA_CAT if m else lookup[v] for v, m in zip(vals, mask_na)], np.int32)
    return dom, codes


class Frame(Keyed):
    """Named, ordered collection of equal-length Columns
    (water/fvec/Frame.java:64). Lockable via DKV per-key locks."""

    def __init__(self, columns: Optional[Dict[str, Column]] = None,
                 key: Optional[str] = None):
        super().__init__(key or Key.make("Frame"))
        self._names: List[str] = []
        self._cols: Dict[str, Column] = {}
        if columns:
            for name, col in columns.items():
                self.add(name, col)

    # -- structure --------------------------------------------------------
    @property
    def names(self) -> List[str]:
        return list(self._names)

    @property
    def columns(self) -> List[Column]:
        return [self._cols[n] for n in self._names]

    @property
    def ncols(self) -> int:
        return len(self._names)

    @property
    def nrows(self) -> int:
        return self._cols[self._names[0]].nrows if self._names else 0

    nrow = nrows  # h2o-py alias
    ncol = ncols

    @property
    def types(self) -> Dict[str, str]:
        return {n: self._cols[n].ctype for n in self._names}

    def col(self, name_or_idx: Union[str, int]) -> Column:
        if isinstance(name_or_idx, int):
            return self._cols[self._names[name_or_idx]]
        return self._cols[name_or_idx]

    def __getitem__(self, sel):
        if isinstance(sel, (str, int)):
            return self.col(sel)
        if isinstance(sel, (list, tuple)):
            return self.subframe(sel)
        raise TypeError(f"bad frame selector {sel!r}")

    def __contains__(self, name: str) -> bool:
        return name in self._cols

    def add(self, name: str, col: Column) -> "Frame":
        if self._names and col.nrows != self.nrows:
            raise ValueError(f"column {name!r} has {col.nrows} rows, frame has {self.nrows}")
        if name in self._cols:
            raise ValueError(f"duplicate column {name!r}")
        self._names.append(name)
        self._cols[name] = col
        return self

    def replace(self, name: str, col: Column) -> "Frame":
        """Copy-on-write column replacement (vs H2O inflate-on-write)."""
        if name not in self._cols:
            return self.add(name, col)
        if col.nrows != self.nrows:
            raise ValueError("row mismatch")
        self._cols[name] = col
        return self

    def swap_columns(self, mapping: Dict[str, Column]) -> "Frame":
        """Atomically swap EVERY column for a same-length replacement —
        the streaming-append path (ingest/chunked.append_csv) grows all
        columns to the new row count in one step, which replace()'s
        per-column row guard would reject mid-swap. The mapping must
        cover exactly the frame's columns and agree on one row count."""
        if set(mapping) != set(self._names):
            raise ValueError("swap_columns must cover exactly the frame's "
                             "columns")
        rows = {c.nrows for c in mapping.values()}
        if len(rows) > 1:
            raise ValueError(f"swap_columns row counts disagree: {rows}")
        # ONE reference rebind (GIL-atomic). A reader calling col() per
        # column MAY observe mixed generations across calls, which is
        # benign by the append invariant: the new columns preserve rows
        # [0, old_n) bitwise (cat codes renumber WITH their domain inside
        # one Column, so label semantics hold), and a reader can only
        # target the appended rows after reading the new nrows — i.e.
        # after this rebind is visible, when every col() already returns
        # the new generation (attribute reads are monotonic under the
        # GIL). Appends that grow the PADDED capacity may transiently
        # hand a mixed-layout column set to a packed scorer — a per-
        # request retryable layout miss, not corruption.
        self._cols = {nm: mapping[nm] for nm in self._names}
        return self

    def drop(self, name: str) -> "Frame":
        self._names.remove(name)
        self._cols.pop(name)
        return self

    def rename(self, old: str, new: str) -> "Frame":
        i = self._names.index(old)
        self._names[i] = new
        self._cols[new] = self._cols.pop(old)
        return self

    def subframe(self, names: Sequence[Union[str, int]], key: Optional[str] = None) -> "Frame":
        fr = Frame(key=key)
        for n in names:
            nm = self._names[n] if isinstance(n, int) else n
            fr.add(nm, self._cols[nm])
        return fr

    def cbind(self, other: "Frame") -> "Frame":
        fr = Frame()
        for n in self._names:
            fr.add(n, self._cols[n])
        for n in other._names:
            nm = n
            while nm in fr._cols:
                nm = nm + "0"  # H2O dedup suffix behavior
            fr.add(nm, other._cols[n])
        return fr

    # -- sharded data plane -----------------------------------------------
    def sharded_view(self, names: Optional[Sequence[str]] = None):
        """Row-sharded data-plane view (core/sharded_frame.ShardedFrame):
        named row axis + NamedSharding over this frame's device columns,
        or None when a named column has no device data (strings) or the
        layouts disagree. The fused scoring and tree-input paths pack
        through it so full columns are never staged on the coordinator."""
        from h2o3_tpu.core.sharded_frame import ShardedFrame

        return ShardedFrame.of(self, names)

    # -- materialization --------------------------------------------------
    def to_pandas(self):
        import pandas as pd

        # python string storage, scoped: pandas-3's pyarrow-backed string
        # construction has crashed (SIGSEGV) under the threaded REST server
        # in this environment; keep the workaround out of global state
        with pd.option_context("mode.string_storage", "python"):
            return pd.DataFrame({n: self._cols[n].values()
                                 for n in self._names})

    def to_numpy(self) -> np.ndarray:
        return np.column_stack([self._cols[n].to_numpy() for n in self._names])

    @staticmethod
    def from_numpy(arr: np.ndarray, names: Optional[Sequence[str]] = None,
                   key: Optional[str] = None) -> "Frame":
        arr = np.atleast_2d(arr)
        names = list(names) if names else [f"C{i+1}" for i in range(arr.shape[1])]
        fr = Frame(key=key)
        for i, n in enumerate(names):
            fr.add(n, Column.from_numpy(arr[:, i]))
        return fr

    @staticmethod
    def from_pandas(df, key: Optional[str] = None,
                    column_types: Optional[Dict[str, str]] = None) -> "Frame":
        fr = Frame(key=key)
        for n in df.columns:
            s = df[n]
            ctype = (column_types or {}).get(n)
            if ctype is None and (s.dtype.name == "category" or s.dtype.kind in "OUS"):
                # strings with low-ish cardinality -> enum, like ParseSetup guessing
                ctype = T_CAT
            fr.add(str(n), Column.from_numpy(s.to_numpy(), ctype=ctype))
        return fr

    # -- stats ------------------------------------------------------------
    def summary(self) -> Dict[str, dict]:
        out = {}
        for n in self._names:
            c = self._cols[n]
            if c.is_numeric or c.ctype == T_TIME:
                r = c.rollups
                out[n] = {"type": c.ctype, "min": r.min, "max": r.max,
                          "mean": r.mean, "sigma": r.sigma, "na_count": r.na_count}
            elif c.is_categorical:
                r = c.rollups
                out[n] = {"type": c.ctype, "cardinality": c.cardinality,
                          "na_count": r.na_count}
            else:
                out[n] = {"type": c.ctype}
        return out

    def __repr__(self) -> str:
        return f"<Frame {self._key} {self.nrows}x{self.ncols} {self._names[:8]}>"

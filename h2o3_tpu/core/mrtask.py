"""MRTask — the distributed compute harness.

Reference design: fork/join map/reduce over chunks with a binary-tree RPC
fan-out across nodes (water/MRTask.java:63; dfork :455, remote_compute :572,
compute2 :596, reduce3 :751) and user hooks map/reduce/setupLocal/postGlobal.

TPU-native design (SURVEY.md §7): a map over row shards is a
`shard_map`-decorated function on the mesh; the reduce is an XLA collective
(`psum`/`pmax`/...) over ICI — the binary node tree AND the lock-free local
CAS reductions both collapse into one compiler-scheduled all-reduce.
setupLocal/postGlobal become host code around the jitted region.

Two entry points:
- `map_reduce(fn, cols)`: fn(shard_arrays...) -> pytree of partials, psum'd
  across shards. Equivalent of `new MRTask(){map/reduce}.doAll(frame)`.
- `map_chunks(fn, cols)`: fn(shard_arrays...) -> same-length output
  shard(s); equivalent of doAll(outputTypes, frame) producing NewChunks
  (water/MRTask.java:224 outputFrame).

Both run inside one jit: XLA fuses the per-shard body and inserts the
collectives.
"""

from __future__ import annotations

from h2o3_tpu.compat import shard_map as _compat_shard_map
import functools
from typing import Callable, Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P

from h2o3_tpu.core.frame import Column


def _mesh():
    from h2o3_tpu.core.runtime import cluster

    return cluster().mesh


@functools.lru_cache(maxsize=512)
def _build_map_reduce(fn, n_in: int, mesh):
    @jax.jit
    def run(*arrays):
        def body(*chunks):
            partial = fn(*chunks)
            return jax.tree.map(lambda x: jax.lax.psum(x, "rows"), partial)

        shard = _compat_shard_map(
            body, mesh=mesh,
            in_specs=tuple(P("rows") for _ in range(n_in)),
            out_specs=P(),
        )
        return shard(*arrays)

    return run


def map_reduce(fn: Callable, cols: Sequence[Column]):
    """doAll-style map/reduce: fn sees this shard's slice of each column and
    returns a pytree of reduction partials; result is the psum over shards."""
    from h2o3_tpu.core.failure import faultpoint

    faultpoint("mrtask.map_reduce")     # chaos hook (core/failure.py)
    arrays = tuple(c.data for c in cols)
    return _build_map_reduce(fn, len(arrays), _mesh())(*arrays)


@functools.lru_cache(maxsize=512)
def _build_map_chunks(fn, n_in: int, n_out: int, mesh):
    @jax.jit
    def run(*arrays):
        shard = _compat_shard_map(
            fn, mesh=mesh,
            in_specs=tuple(P("rows") for _ in range(n_in)),
            out_specs=tuple(P("rows") for _ in range(n_out)) if n_out > 1 else P("rows"),
        )
        return shard(*arrays)

    return run


def map_chunks(fn: Callable, cols: Sequence[Column], n_out: int = 1):
    """doAll(newtypes)-style: shard-local transform producing new row-aligned
    output arrays (the NewChunk path, MRTask.java:224-249)."""
    arrays = tuple(c.data for c in cols)
    return _build_map_chunks(fn, len(arrays), n_out, _mesh())(*arrays)


def new_column(fn: Callable, cols: Sequence[Column], ctype: Optional[str] = None) -> Column:
    """Build one output Column from input columns via a shard-local fn."""
    out = map_chunks(fn, cols, n_out=1)
    c0 = cols[0]
    return Column.from_device(out, ctype or c0.ctype, c0.nrows)


class LocalMR:
    """Node-local parallel loop (water/LocalMR.java). On TPU the analog is a
    vmapped/fused jit body; provided for API parity."""

    @staticmethod
    def run(fn: Callable, xs):
        return jax.vmap(fn)(xs)

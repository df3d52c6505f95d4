"""The level-wise histogram build and row router of the host-driven tree
loop, which IsolationForest (isofor.py) runs: its random splits need a
count histogram a level and no split search. GBM and DRF grow a tree in
one device program (device_tree.py) and use nothing here.

Reference: hex/tree/ScoreBuildHistogram2.java:60 — per-row bin increments
into DHistogram _vals[] (w/wY/wYY triples, DHistogram.java:62-90) with
lock-free CAS adds, tree-reduced across nodes via MRTask.

TPU-native design: one scatter-add per level — every (row, feature) pair
contributes (w, w·y, w·y²) at index  node·TB + offset[f] + bin  into a
zeroed (nodes·TB, 3) accumulator; the per-shard partials are psum'd over
the mesh 'rows' axis (the MRTask reduce tree AND the CAS atomics both
collapse into one XLA all-reduce). No atomics, no locks: scatter-add is
deterministic on TPU, and XLA fuses the residual computation feeding `y`
into the same program.
"""

from __future__ import annotations

from h2o3_tpu.compat import shard_map as _compat_shard_map
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _mesh():
    from h2o3_tpu.core.runtime import cluster

    return cluster().mesh


@functools.lru_cache(maxsize=64)
def _build_hist_fn(n_nodes: int, tot_bins: int, F: int, mesh):
    """Jitted (binned, row_node, w, y, offsets) -> (n_nodes, tot_bins, 3).

    Cache key includes the padded node count, so only O(log depth) distinct
    programs compile per (dataset, depth) family.
    """
    from h2o3_tpu.obs import compiles

    def local_hist(binned, row_node, w, y, offsets):
        # binned (n, F) integer bins (narrowest dtype that fits nbins);
        # row_node (n,) int32 (-1 = finalized row)
        valid = row_node >= 0
        node = jnp.maximum(row_node, 0)
        idx = node[:, None] * tot_bins + offsets[None, :] + binned   # (n, F)
        idx = jnp.where(valid[:, None], idx, n_nodes * tot_bins)     # dropped
        wv = jnp.where(valid, w, 0.0)
        vals = jnp.stack([wv, wv * y, wv * y * y], axis=-1)          # (n, 3)
        upd = jnp.broadcast_to(vals[:, None, :], (binned.shape[0], F, 3))
        acc = jnp.zeros((n_nodes * tot_bins, 3), jnp.float32)
        acc = acc.at[idx.reshape(-1)].add(upd.reshape(-1, 3), mode="drop")
        return jax.lax.psum(acc, "rows")

    fn = _compat_shard_map(
        local_hist, mesh=mesh,
        in_specs=(P("rows", None), P("rows"), P("rows"), P("rows"), P()),
        out_specs=P(),
    )

    def run(binned, row_node, w, y, offsets):
        return fn(binned, row_node, w, y, offsets).reshape(n_nodes, tot_bins, 3)

    return compiles.ledgered_jit(
        "tree", run, program=f"hist_level_S{n_nodes}")


def build_histogram(binned, row_node, w, y, spec, n_nodes: int) -> np.ndarray:
    """-> host (n_nodes, tot_bins, 3) float64 histogram (w, wy, wyy)."""
    n_pad = max(1 << (n_nodes - 1).bit_length(), 1)
    fn = _build_hist_fn(n_pad, spec.tot_bins, spec.F, _mesh())
    offsets = jnp.asarray(spec.offsets[:-1], jnp.int32)
    out = fn(binned, row_node, w.astype(jnp.float32), y.astype(jnp.float32), offsets)
    return np.asarray(out, np.float64)[:n_nodes]


@functools.lru_cache(maxsize=64)
def _build_route_fn(S: int, maxB: int, mesh):
    """Jitted row routing for one level.

    Per active slot s: split_feat[s] (-1 ⇒ terminal), left_table[s, bin]
    (precomputed bool incl. NA direction — numeric thresholds, categorical
    subsets and NA all unify into one LUT), child slot ids, and for
    terminals the global leaf id.
    """

    def route(binned, row_node, row_leaf, split_feat, left_table, left_slot,
              right_slot, leaf_id):
        active = row_node >= 0
        node = jnp.maximum(row_node, 0)
        f = split_feat[node]                               # (n,)
        terminal = f < 0
        b = jnp.take_along_axis(binned, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
        go_left = left_table[node, jnp.minimum(b, maxB - 1)]
        new_node = jnp.where(go_left, left_slot[node], right_slot[node])
        new_node = jnp.where(active & ~terminal, new_node, -1)
        new_leaf = jnp.where(active & terminal, leaf_id[node], row_leaf)
        return new_node, new_leaf

    fn = _compat_shard_map(
        route, mesh=mesh,
        in_specs=(P("rows", None), P("rows"), P("rows"), P(), P(), P(), P(), P()),
        out_specs=(P("rows"), P("rows")),
    )
    from h2o3_tpu.obs import compiles

    return compiles.ledgered_jit("tree", fn, program=f"tree_route_S{S}")


def route_rows(binned, row_node, row_leaf, *, split_feat, left_table,
               left_slot, right_slot, leaf_id):
    """Apply one level's split decisions to every row (device)."""
    S = len(split_feat)
    S_pad = max(1 << (S - 1).bit_length(), 1) if S else 1
    maxB = left_table.shape[1] if S else 1

    def pad1(a, fill):
        return np.concatenate([a, np.full(S_pad - S, fill, a.dtype)])

    sf = jnp.asarray(pad1(np.asarray(split_feat, np.int32), -1))
    lt = np.zeros((S_pad, maxB), bool)
    if S:
        lt[:S] = left_table
    fn = _build_route_fn(S_pad, maxB, _mesh())
    return fn(binned, row_node, row_leaf, sf, jnp.asarray(lt),
              jnp.asarray(pad1(np.asarray(left_slot, np.int32), -1)),
              jnp.asarray(pad1(np.asarray(right_slot, np.int32), -1)),
              jnp.asarray(pad1(np.asarray(leaf_id, np.int32), -1)))

"""Host-orchestrated level-wise tree growth — the DEEP-tree fallback.

The single-dispatch heap grower (device_tree.py) lays nodes out at heap
positions, so its memory is O(2^depth): perfect to depth ~10, unusable at
DRF's default depth 20. This module keeps the round-2 design for deep
trees: per level one device histogram (scatter-add + psum, histogram.py),
a host numpy split search over only the ACTIVE nodes (dtree.py), and one
device routing pass — memory O(active nodes), like the reference's
level-wise SharedTree (hex/tree/SharedTree.java:439 scoreAndBuildTrees).

Since round 4 the fit loops use device_tree.py's dense-frontier grower at
EVERY depth; this module remains only behind the public grow_tree() entry
(old single-tree contract with dense leaf ids).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from h2o3_tpu.models.tree.dtree import (HostTree, find_best_splits,
                                        left_table_for)
from h2o3_tpu.models.tree.histogram import build_histogram, route_rows


def grow_tree_host(binned, hist_w, hist_y, spec, *, max_depth: int,
                   min_rows: float, min_split_improvement: float,
                   row_active=None, feat_mask_fn=None,
                   rng: Optional[np.random.Generator] = None):
    """Grow one tree level-wise. Returns (HostTree, row_leaf device array)
    with DENSE leaf ids (tree.n_leaves counts them)."""
    import jax.numpy as jnp

    N = binned.shape[0]
    tree = HostTree()
    row_node = jnp.zeros(N, jnp.int32)
    if row_active is not None:
        row_node = jnp.where(row_active, row_node, -1)
    row_leaf = jnp.full(N, -1, jnp.int32)
    slots = [0]                   # tree nid per active slot

    if max_depth == 0:
        # a stump needs exactly two scalars — summing (w, w·y) over the
        # active rows directly is two device reductions, not a full
        # (nodes, tot_bins, 3) histogram build
        act = row_node >= 0
        w32 = jnp.where(act, jnp.asarray(hist_w, jnp.float32), 0.0)
        wy = float(jnp.sum(w32 * jnp.asarray(hist_y, jnp.float32)))
        tree.nodes[0].weight = float(jnp.sum(w32))
        tree.nodes[0].pred = wy / max(tree.nodes[0].weight, 1e-12)

    for depth in range(max_depth + 1):
        if not slots:
            break
        S = len(slots)
        # the final level never splits, so it never builds a histogram
        # (the max_depth=0 root stats come from the pre-loop reductions)
        if depth < max_depth:
            hist = build_histogram(binned, row_node, hist_w, hist_y, spec, S)
            if depth == 0:
                # root stats ride the level hist already in hand: sum the
                # (w, wy) lanes of feature 0 across its bins
                o, B = int(spec.offsets[0]), int(spec.nbins[0])
                tree.nodes[0].weight = float(hist[0, o:o + B, 0].sum())
                wy = float(hist[0, o:o + B, 1].sum())
                tree.nodes[0].pred = wy / max(tree.nodes[0].weight, 1e-12)
        if depth == max_depth:
            splits = [None] * S
        else:
            feat_mask = feat_mask_fn(S) if feat_mask_fn else None
            splits = find_best_splits(hist, spec, min_rows=min_rows,
                                      min_split_improvement=min_split_improvement,
                                      feat_mask=feat_mask)
        split_feat = np.full(S, -1, np.int32)
        left_slot = np.full(S, -1, np.int32)
        right_slot = np.full(S, -1, np.int32)
        leaf_id = np.full(S, -1, np.int32)
        next_slots: List[int] = []
        for s, sp in enumerate(splits):
            nid = slots[s]
            node = tree.nodes[nid]
            if sp is None:
                leaf_id[s] = tree.finalize_leaf(nid, node.weight, node.pred)
                continue
            node.split = sp
            split_feat[s] = sp.feat
            node.left = tree.new_node(depth + 1)
            node.right = tree.new_node(depth + 1)
            lw, lwy = sp.left_stats
            rw, rwy = sp.right_stats
            tree.nodes[node.left].weight = float(lw)
            tree.nodes[node.left].pred = float(lwy) / max(float(lw), 1e-12)
            tree.nodes[node.right].weight = float(rw)
            tree.nodes[node.right].pred = float(rwy) / max(float(rw), 1e-12)
            left_slot[s] = len(next_slots)
            next_slots.append(node.left)
            right_slot[s] = len(next_slots)
            next_slots.append(node.right)
        maxB = int(spec.nbins.max())
        lt = left_table_for(splits, spec, maxB)
        row_node, row_leaf = route_rows(
            binned, row_node, row_leaf, split_feat=split_feat, left_table=lt,
            left_slot=left_slot, right_slot=right_slot, leaf_id=leaf_id)
        slots = next_slots
    return tree, row_leaf

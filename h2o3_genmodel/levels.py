"""The level-ordered view of a stored forest that the device walk reads
(h2o3_tpu/models/tree/compressed.py _walk_tree): numpy only, so the
standalone runner (h2o3_genmodel.aot) rebuilds the exported program's
inputs from the stored arrays of an artifact with the very function the
framework lays its own forests out with."""

from __future__ import annotations

import numpy as np


def walk_widths(max_depth: int, M: int) -> tuple:
    """Entries of a (M,) node table that step d of the walk reads, for
    d < max_depth: a row at depth d stands on one of at most 2^d nodes (or
    on a leaf it reached earlier, which it keeps). Static, from shapes."""
    return tuple(min(1 << d, M) for d in range(max_depth))


# what a walk program takes after the rows, in order: level_view's tuple
WALK_ARGS = ("nodes", "cat_words", "tree_class", "na_bins", "starts")
# rows of `nodes`; the walk's step reads the first four, five in a tree
# with an enum split
FEAT, THRESH, NA_LEFT, LEFT, CAT_SPLIT, LEAF_BITS, STORED_ID = range(7)
LEVEL_START, CAT_START = range(2)        # rows of `starts`


def level_view(stored, max_depth: int) -> tuple:
    """A forest's stored arrays by name (the keys of an artifact's
    forest.npz, the attributes of a CompressedForest) laid out for the
    walk, on the host: a tree's nodes breadth first from node 0, each depth
    a contiguous run, a node's children side by side (right = left + 1, so
    no `right`), nodes nothing reaches last. -> WALK_ARGS:

      nodes (T, 7, M) int32    a position's FEAT, THRESH, NA_LEFT (0 | 1)
                               as stored; LEFT, the position of the left
                               child; CAT_SPLIT, the enum split's row in
                               its level's slice of cat_words, -1 numeric;
                               LEAF_BITS, the f32 leaf value's bits;
                               STORED_ID, the stored node id. One array: a
                               program's dispatch costs the host by the
                               argument, and a level is one slice of it
      cat_words (C', W) uint32 the subsets of the splits the walk reaches,
                               by tree, depth and position, 32 bins a word
                               (bits past the last bin repeat it: a bin
                               clamped to the words is clamped to the bins);
                               no row where it reaches none, which a walk
                               program reads from the shape: it then holds
                               no subset test at all
      tree_class, na_bins      as stored
      starts (T, 2, max(max_depth, 1)) int32
                               LEVEL_START, CAT_START: where step d's
                               slice of walk_widths' width starts in
                               `nodes`, and of as many rows in cat_words:
                               depth d's run, moved down where it would
                               pass the end

    Trees grown by tree_program are stored in this order already
    (STORED_ID is then the identity); imported and host-built ones need
    not be."""
    feat, left, right, cat_split, cat_table = (
        stored[k] for k in ("feat", "left", "right", "cat_split",
                            "cat_table"))
    feat = np.asarray(feat, np.int32)
    left, right = np.asarray(left, np.int32), np.asarray(right, np.int32)
    cat_split = np.asarray(cat_split, np.int32)
    cat_table = np.asarray(cat_table, bool)
    T, M = feat.shape
    D = int(max_depth)
    stored_id = np.empty((T, M), np.int32)
    depth_of = np.empty((T, M), np.int64)          # of a position, < D
    run_start = np.full((T, D + 1), M, np.int64)   # depth d's run, unclamped
    for t in range(T):
        runs, at, level = [], 0, np.zeros(1, np.int32)
        for d in range(D + 1):
            run_start[t, d] = at
            runs.append(level)
            at += level.size
            inner = level[feat[t, level] >= 0]
            if inner.size == 0:
                run_start[t, d + 1:] = at
                break
            if d == D:
                raise ValueError(
                    f"tree {t} has a split at depth {D}, the forest's "
                    "max_depth, where every node must be a leaf")
            level = np.stack([left[t, inner], right[t, inner]], 1).ravel()
        reached = np.concatenate(runs)
        if at > M or np.unique(reached).size != at:
            raise ValueError(f"tree {t}: a node has two parents")
        stored_id[t, :at] = reached
        stored_id[t, at:] = np.setdiff1d(np.arange(M, dtype=np.int32),
                                         reached)
        depth_of[t] = np.searchsorted(run_start[t, 1:max(D, 1)],
                                      np.arange(M), side="right")
    position = np.empty_like(stored_id)
    np.put_along_axis(position, stored_id,
                      np.arange(M, dtype=np.int32)[None, :], axis=1)

    def moved(table):
        return np.take_along_axis(np.asarray(table), stored_id, axis=1)

    feat_lv = moved(feat)
    left_lv = np.where(
        feat_lv >= 0,
        np.take_along_axis(position, np.clip(moved(left), 0, M - 1), axis=1),
        0).astype(np.int32)
    widths = np.array(walk_widths(max(D, 1), M), np.int64)
    level_start = np.minimum(run_start[:, :max(D, 1)], M - widths[None, :])

    # the subsets the walk reaches, in (tree, position) order = by depth
    cs_lv = moved(cat_split)
    above_last = np.arange(M)[None, :] < run_start[:, D:D + 1]
    is_sub = above_last & (feat_lv >= 0) & (cs_lv >= 0)
    new_row = np.cumsum(is_sub.ravel()).reshape(T, M) - is_sub
    rows = cat_table[cs_lv[is_sub]]
    C, maxB = rows.shape
    W = -(-maxB // 32)
    bits = np.concatenate(
        [rows, np.repeat(rows[:, -1:], W * 32 - maxB, axis=1)], axis=1)
    cat_words = (bits.reshape(C, W, 32).astype(np.uint32)
                 << np.arange(32, dtype=np.uint32)).sum(
                     axis=2, dtype=np.uint32)
    before = np.concatenate([[0], np.cumsum(is_sub.ravel())])
    flat = np.arange(T)[:, None] * M + run_start[:, :max(D, 1)]
    cat_start = np.minimum(before[flat],
                           C - np.minimum(widths, C)[None, :])
    cs_local = np.where(
        is_sub, new_row - np.take_along_axis(cat_start, depth_of, axis=1),
        -1).astype(np.int32)
    nodes = np.stack([
        feat_lv, moved(np.asarray(stored["thresh_bin"], np.int32)),
        moved(np.asarray(stored["na_left"], bool)).astype(np.int32),
        left_lv, cs_local,
        moved(np.asarray(stored["leaf_val"], np.float32)).view(np.int32),
        stored_id], axis=1)
    return (nodes, cat_words, np.asarray(stored["tree_class"], np.int32),
            np.asarray(stored["na_bins"], np.int32),
            np.stack([level_start, cat_start], axis=1).astype(np.int32))

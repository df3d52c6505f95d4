"""Host-side tree structure: what a grown tree is assembled into.

Reference: hex/tree/DTree.java. GBM and DRF grow a tree in one device
program (device_tree.py: histogram, split search with the squared-error
gain SE(parent) - SE(left) - SE(right), SE = wyy - wy²/w, routing) and
build a HostTree from its fetched tables (host_tree_from_packed);
IsolationForest builds one a level at a time from random splits
(isofor.py). Numeric thresholds, categorical subsets and the side of the
NA bin unify into one routing table a node (left_table_for).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np


@dataclass
class Split:
    feat: int
    is_cat: bool
    thresh_bin: int               # numeric: go left iff bin <= thresh_bin
    left_bins: Optional[np.ndarray]   # categorical: bool (B_f-1,) over codes
    na_left: bool
    gain: float
    left_stats: tuple             # (w, wy)
    right_stats: tuple


@dataclass
class TreeNode:
    """One node of the (host) tree being grown; compressed after training."""

    nid: int
    depth: int
    split: Optional[Split] = None
    left: int = -1
    right: int = -1
    leaf_value: float = 0.0
    leaf_id: int = -1             # dense leaf numbering for GammaPass
    weight: float = 0.0
    pred: float = 0.0             # node mean (wy/w) — DRF leaf / pruning


def left_table_for(splits: List[Optional[Split]], spec, maxB: int) -> np.ndarray:
    """(S, maxB) bool routing LUT: entry [s, b] = row with bin b goes left.
    NA bin (B_f-1) carries the NA direction; unifies numeric + categorical."""
    S = len(splits)
    lt = np.zeros((S, maxB), bool)
    for s, sp in enumerate(splits):
        if sp is None:
            continue
        B = int(spec.nbins[sp.feat])
        if sp.is_cat:
            lt[s, :B - 1] = sp.left_bins
        else:
            lt[s, :sp.thresh_bin + 1] = True
        lt[s, B - 1] = sp.na_left
    return lt


class HostTree:
    """Growable host tree; finalized into compressed arrays per tree."""

    def __init__(self):
        self.nodes: List[TreeNode] = [TreeNode(0, 0)]
        self.n_leaves = 0

    def new_node(self, depth: int) -> int:
        nid = len(self.nodes)
        self.nodes.append(TreeNode(nid, depth))
        return nid

    def finalize_leaf(self, nid: int, weight: float, pred: float) -> int:
        n = self.nodes[nid]
        n.leaf_id = self.n_leaves
        n.weight = weight
        n.pred = pred
        self.n_leaves += 1
        return n.leaf_id

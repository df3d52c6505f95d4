"""Compressed forest: stacked tree arrays + vectorized device scoring.

Reference: hex/tree/CompressedTree.java — trees serialized to flat byte
arrays, scored row-at-a-time by walking the bytes (score0); genmodel
mirrors the walk for MOJOs.

TPU-native design: the forest IS a pytree of dense arrays shaped
(n_trees, max_nodes): feat / thresh_bin / na_left / left / right /
leaf_val, plus one shared categorical-subset LUT. Scoring every row
through every tree is a lax.scan over trees of a lax.fori_loop pointer
chase — all rows advance one level per step in lockstep (SIMD traversal),
bins replace raw feature comparisons so test data is binned once with the
training edges and the traversal is pure int compares. Row-sharded input
⇒ embarrassingly parallel over the mesh.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import numpy as np


class CompressedForest:
    """Stacked per-node arrays; construction from HostTrees in builder code.

    Arrays (T, M): feat int32 (-1 leaf), thresh_bin int32, na_left bool,
    left/right int32, leaf_val f32, cat_split int32 (-1 numeric, else row in
    cat_table). cat_table (C, maxB) bool. tree_class (T,) int32 for
    multinomial tree→class mapping. na_bins (F,) int32 = NA bin per feature.
    """

    def __init__(self, feat, thresh_bin, na_left, left, right, leaf_val,
                 cat_split, cat_table, tree_class, na_bins, max_depth: int,
                 init_f: float = 0.0, nclasses: int = 1):
        self.feat = feat
        self.thresh_bin = thresh_bin
        self.na_left = na_left
        self.left = left
        self.right = right
        self.leaf_val = leaf_val
        self.cat_split = cat_split
        self.cat_table = cat_table
        self.tree_class = tree_class
        self.na_bins = na_bins
        self.max_depth = int(max_depth)
        self.init_f = float(init_f)
        self.nclasses = int(nclasses)
        self.init_class = None        # (K,) per-class prior margins (multinomial)
        # host-only explanation metadata (TreeSHAP covers, FeatureInteraction
        # gains): (T, M) or None for forests built before they were recorded
        self.gain = None
        self.cover = None

    @property
    def n_trees(self) -> int:
        return int(self.feat.shape[0])

    @staticmethod
    def from_host_trees(trees: List, spec, *, tree_class=None,
                        max_depth: int, init_f: float = 0.0, nclasses: int = 1
                        ) -> "CompressedForest":
        T = len(trees)
        M = max(max(len(t.nodes) for t in trees), 1)
        feat = np.full((T, M), -1, np.int32)
        thresh = np.zeros((T, M), np.int32)
        na_left = np.zeros((T, M), bool)
        left = np.zeros((T, M), np.int32)
        right = np.zeros((T, M), np.int32)
        leaf_val = np.zeros((T, M), np.float32)
        cat_split = np.full((T, M), -1, np.int32)
        cat_rows = []
        maxB = int(spec.nbins.max())
        gain = np.zeros((T, M), np.float32)
        cover = np.zeros((T, M), np.float32)
        for ti, tree in enumerate(trees):
            for n in tree.nodes:
                cover[ti, n.nid] = n.weight
                if n.split is None:
                    leaf_val[ti, n.nid] = n.leaf_value
                    continue
                s = n.split
                feat[ti, n.nid] = s.feat
                na_left[ti, n.nid] = s.na_left
                left[ti, n.nid] = n.left
                right[ti, n.nid] = n.right
                gain[ti, n.nid] = max(s.gain, 0.0)
                if s.is_cat:
                    row = np.zeros(maxB, bool)
                    row[: len(s.left_bins)] = s.left_bins
                    cat_split[ti, n.nid] = len(cat_rows)
                    cat_rows.append(row)
                else:
                    thresh[ti, n.nid] = s.thresh_bin
        cat_table = (np.stack(cat_rows) if cat_rows
                     else np.zeros((1, maxB), bool))
        tc = (np.asarray(tree_class, np.int32) if tree_class is not None
              else np.zeros(T, np.int32))
        out = CompressedForest(feat, thresh, na_left, left, right, leaf_val,
                               cat_split, cat_table, tc,
                               (spec.nbins - 1).astype(np.int32),
                               max_depth=max_depth, init_f=init_f,
                               nclasses=nclasses)
        out.gain = gain
        out.cover = cover
        return out

    @staticmethod
    def concat(a: "CompressedForest", b: "CompressedForest", *,
               scale_a: float = 1.0, scale_b: float = 1.0
               ) -> "CompressedForest":
        """Append forest b's trees after forest a's (training continuation,
        hex/Model.java:365 _checkpoint). Node tables are padded to the wider
        forest; b's cat-subset rows are appended with their split indices
        shifted. scale_a/scale_b rescale leaf values (DRF resume: leaves are
        stored pre-divided by tree count, so both sides rescale to
        n_side/n_total)."""
        assert a.nclasses == b.nclasses, (a.nclasses, b.nclasses)
        M = max(a.feat.shape[1], b.feat.shape[1])

        def pad(x, fill):
            T, m = x.shape
            if m == M:
                return np.asarray(x)
            out = np.full((T, M), fill, np.asarray(x).dtype)
            out[:, :m] = x
            return out

        maxB = max(a.cat_table.shape[1], b.cat_table.shape[1])

        def padB(t):
            if t.shape[1] == maxB:
                return np.asarray(t)
            out = np.zeros((t.shape[0], maxB), bool)
            out[:, : t.shape[1]] = t
            return out

        b_cs = pad(b.cat_split, -1).copy()
        b_cs[b_cs >= 0] += a.cat_table.shape[0]
        cat = lambda fa, fb: np.concatenate([fa, fb], axis=0)  # noqa: E731
        out = CompressedForest(
            cat(pad(a.feat, -1), pad(b.feat, -1)),
            cat(pad(a.thresh_bin, 0), pad(b.thresh_bin, 0)),
            cat(pad(a.na_left, False), pad(b.na_left, False)),
            cat(pad(a.left, 0), pad(b.left, 0)),
            cat(pad(a.right, 0), pad(b.right, 0)),
            cat(pad(a.leaf_val, 0).astype(np.float32) * np.float32(scale_a),
                pad(b.leaf_val, 0).astype(np.float32) * np.float32(scale_b)),
            cat(pad(a.cat_split, -1), b_cs),
            cat(padB(a.cat_table), padB(b.cat_table)),
            np.concatenate([np.asarray(a.tree_class), np.asarray(b.tree_class)]),
            np.asarray(a.na_bins),
            max_depth=max(a.max_depth, b.max_depth),
            init_f=a.init_f, nclasses=a.nclasses)
        out.init_class = a.init_class
        ga = getattr(a, "gain", None)
        gb = getattr(b, "gain", None)
        if ga is not None and gb is not None:
            out.gain = cat(pad(ga, 0), pad(gb, 0))
            out.cover = cat(pad(a.cover, 0), pad(b.cover, 0))
        return out

    # -- device scoring ----------------------------------------------------
    def arrays(self):
        import jax.numpy as jnp

        return tuple(jnp.asarray(a) for a in (
            self.feat, self.thresh_bin, self.na_left, self.left, self.right,
            self.leaf_val, self.cat_split, self.cat_table, self.tree_class,
            self.na_bins))

    @property
    def per_class_trees(self) -> bool:
        """True when trees are grown one-per-class (multinomial, or DRF
        binomial_double_trees — class-1 trees present at nclasses==2):
        the traversal must keep K class slots, not collapse to one."""
        return self.nclasses > 2 or (
            self.nclasses == 2
            and int(np.asarray(self.tree_class).max(initial=0)) > 0)

    @functools.cached_property
    def walk_form(self) -> str:
        """Which form of the walk this forest's tables select (the label of
        h2o3_forest_walk_total): the row's bin is always read by select;
        `select` / `gather` says how a TPU reads the node tables (_at_node's
        rule from M), `+cat` that some tree takes the categorical branch of
        _walk_tree's cond."""
        return table_form(self.feat.shape[1]) + (
            "+cat" if (np.asarray(self.cat_split) >= 0).any() else "")

    def count_walk(self) -> None:
        from h2o3_tpu.obs import metrics

        metrics.inc("h2o3_forest_walk_total", form=self.walk_form)

    def predict_binned(self, binned):
        """binned (N, F) integer bins (any width) → (N,) sums (regression/binomial margin) or
        (N, K) per-class margins (multinomial / double-trees binomial)."""
        import jax.numpy as jnp

        fn = _traverse_fn(self.max_depth, self.nclasses,
                          self.per_class_trees)
        out = fn(binned, *self.arrays())
        self.count_walk()
        if self.init_class is not None:
            return out + jnp.asarray(self.init_class)[None, :]
        return out + self.init_f

    def leaf_index(self, binned):
        """(N, T) leaf node id per tree (used by RuleFit/TreeSHAP/partial)."""
        fn = _leaf_fn(self.max_depth)
        self.count_walk()
        return fn(binned, *self.arrays())


# node tables up to this many entries are read by compare-and-select over
# the table axis (one fused reduce a table a level, cost by the row and the
# entry); wider ones (DRF at depth 20: 10^4-10^5 nodes a tree) by a gather
# from the (M,) table (cost by the row, ~6 ns a row a table). Measured on a
# v5e, 1M rows (PERF.md §6, PR 27): select ahead 8x at 127 and 255 entries,
# 6.5x at 1,023, 1.9x at 3,997, the largest measured; the lines would cross
# near 8,000
_SELECT_MAX_NODES = 4096


def table_form(entries: int) -> str:
    """How a TPU reads a table of `entries` at a per-row index (_at_node's
    one rule, and the label the walk's and the route's counters carry)."""
    return "select" if entries <= _SELECT_MAX_NODES else "gather"


def _bin_at(binned, fi, na_bins=None):
    """(binned[n, fi[n]], is it fi[n]'s NA bin) for every row n, with no
    gather: compare-and-select over the feature axis, then a sum in which
    one term is not zero. Exact on integers. A per-row gather has no
    hardware on a TPU (19 ns a row); this reads the F columns of the matrix
    once, rows on the lanes, and fuses into reduces that materialise no
    (N, F) intermediate. Without `na_bins` (the tree program's routing,
    whose tables hold the NA bin's side) the flag is None."""
    import jax.numpy as jnp

    hit = jnp.arange(binned.shape[1], dtype=jnp.int32)[None, :] == fi[:, None]
    b = jnp.sum(jnp.where(hit, binned, 0), axis=1, dtype=jnp.int32)
    if na_bins is None:
        return b, None
    return b, jnp.any(hit & (binned == na_bins[None, :]), axis=1)


def _tables_by_gather(node, *tables):
    return tuple(t[node] for t in tables)


def _tables_by_select(node, *tables):
    import jax.numpy as jnp

    M = tables[0].shape[0]
    hit = jnp.arange(M, dtype=jnp.int32)[None, :] == node[:, None]
    return tuple(
        jnp.any(hit & t[None, :], axis=1) if t.dtype == jnp.bool_
        else jnp.sum(jnp.where(hit, t[None, :], 0), axis=1, dtype=t.dtype)
        for t in tables)


def _at_node(tables, node):
    """t[node] for each (M,) node table t. On a TPU, by compare-and-select
    over the table axis while it is narrow, by gather from the table beyond
    that (the operand of that gather is the table: it never carries the
    rows). XLA:CPU has a gather of its own and pays M times for the select
    (6.6x slower at M = 63, and its parallel reduces starve the 8-device
    test mesh's collectives under load), so there the tables are always
    gathered: chosen when the program is lowered, so one compiled for a TPU
    on a CPU host (AOT export) gets the TPU's form. Integers and bools
    only, so either form returns the same bits."""
    import jax

    if table_form(tables[0].shape[0]) == "gather":
        return _tables_by_gather(node, *tables)
    return jax.lax.platform_dependent(node, *tables, cpu=_tables_by_gather,
                                      default=_tables_by_select)


def _step(node, tree, binned, cat_table, na_bins, with_cat: bool):
    """One level of the lockstep walk: every row moves from `node` to its
    child (a leaf stays). The ONE step margins, leaf ids and everything
    built on them share. No operand of a gather here carries the row axis.
    `with_cat` is static: the categorical lookup is traced only into the
    walk of a tree that has a categorical split."""
    import jax.numpy as jnp

    at = _at_node(tree if with_cat else tree[:5], node)
    f, t, na_goes_left, lft, rgt = at[:5]
    b, is_na = _bin_at(binned, jnp.maximum(f, 0), na_bins)
    go_left = b <= t
    if with_cat:
        csid = at[5]
        cat_left = cat_table[jnp.maximum(csid, 0),
                             jnp.minimum(b, cat_table.shape[1] - 1)]
        go_left = jnp.where(csid >= 0, cat_left, go_left)
    go_left = jnp.where(is_na, na_goes_left, go_left)
    return jnp.where(f < 0, node, jnp.where(go_left, lft, rgt))


def _walk_tree(binned, tree, cat_table, na_bins, max_depth: int):
    """(N,) node id each row ends in after max_depth + 1 steps of one tree
    (tree = its feat, thresh, na_left, left, right, cat_split rows). Which
    of the two step forms runs is read from the tree itself, on the device:
    a tree with no categorical split (every tree of a numeric forest) never
    executes the (C, maxB) lookup it would only discard."""
    import jax
    import jax.numpy as jnp

    def walk(with_cat: bool):
        def run(node):
            return jax.lax.fori_loop(
                0, max_depth + 1,
                lambda _, n: _step(n, tree, binned, cat_table, na_bins,
                                   with_cat), node)
        return run

    # the carry is derived from `binned` so it carries its type: under
    # shard_map the rows vary over the mesh axis and a fresh jnp.zeros
    # would not, which the loop carry check rejects; under plain jit this
    # is the same zeros
    node0 = jnp.zeros_like(binned[:, 0], dtype=jnp.int32)
    *_, cat_split = tree
    return jax.lax.cond(jnp.any(cat_split >= 0), walk(True), walk(False),
                        node0)


def _forest_margins(binned, feat, thresh, na_left, left, right, leaf_val,
                    cat_split, cat_table, tree_class, na_bins,
                    max_depth: int, K: int):
    """Traceable core of the lockstep traversal: (N, F) integer bins →
    (N,) / (N, K) leaf-value sums. Shared verbatim by the per-request
    traversal (_traverse_fn) and the serving fast path's fused program
    (_fused_score_fn) so both produce bitwise-identical margins."""
    import jax
    import jax.numpy as jnp

    N = binned.shape[0]

    def walk_one_tree(acc, tree):
        tf, tt, tnl, tl, tr, tlv, tcs, tcls = tree
        node = _walk_tree(binned, (tf, tt, tnl, tl, tr, tcs), cat_table,
                          na_bins, max_depth)
        contrib = tlv[node]
        if K > 1:
            acc = acc.at[:, tcls].add(contrib)
        else:
            acc = acc + contrib
        return acc, None

    # typed like the rows it walks (see _walk_tree)
    acc0 = jnp.zeros_like(binned[:, 0], dtype=jnp.float32)
    if K > 1:
        acc0 = jnp.broadcast_to(acc0[:, None], (N, K))
    with jax.named_scope("walk"):      # metadata: device time by scope
        acc, _ = jax.lax.scan(
            walk_one_tree, acc0,
            (feat, thresh, na_left, left, right, leaf_val, cat_split,
             tree_class))
    return acc


@functools.lru_cache(maxsize=32)
def _traverse_fn(max_depth: int, nclasses: int, per_class: bool = False):
    import jax

    K = nclasses if (nclasses > 2 or per_class) else 1

    def run(binned, feat, thresh, na_left, left, right, leaf_val,
            cat_split, cat_table, tree_class, na_bins):
        return _forest_margins(binned, feat, thresh, na_left, left, right,
                               leaf_val, cat_split, cat_table, tree_class,
                               na_bins, max_depth, K)

    from h2o3_tpu.obs import compiles

    return compiles.ledgered_jit("tree", run, program="forest_traverse")


def _bin_features(X, edges, is_cat, na_bins):
    """Traceable binning core: (N, F) raw float32 features → (N, F) int32
    bins, bitwise-matching BinSpec.bin_columns (numeric bin = #edges < x ==
    searchsorted side='left' with +inf pad lanes never counting;
    categorical bin = code, NA/out-of-range clamped to the feature's NA
    bin). Shared by the fused score and fused leaf programs so every
    explainability output bins exactly like serving does."""
    import jax.numpy as jnp

    nb = na_bins[None, :]
    num_b = jnp.sum(edges[None, :, :] < X[:, :, None],
                    axis=-1).astype(jnp.int32)
    num_b = jnp.where(jnp.isnan(X), nb, num_b)
    # categorical: NaN→-1 before the int cast (NaN→int is undefined)
    codes = jnp.where(jnp.isnan(X), -1.0, X).astype(jnp.int32)
    cat_b = jnp.where((codes < 0) | (codes >= nb), nb, codes)
    return jnp.where(is_cat[None, :], cat_b, num_b)


def _forest_leaves(binned, feat, thresh, na_left, left, right, cat_split,
                   cat_table, na_bins, max_depth: int):
    """Traceable leaf-walk core: (N, F) integer bins → (N, T) leaf node
    ids. The walk is _forest_margins' own (_walk_tree), so the leaf a row
    lands in is by construction the leaf whose value the margin summed —
    shared by the per-request _leaf_fn and the fused leaf programs."""
    import jax
    import jax.numpy as jnp

    def walk(carry, tree):
        return carry, _walk_tree(binned, tree, cat_table, na_bins, max_depth)

    _, leaves = jax.lax.scan(
        walk, None, (feat, thresh, na_left, left, right, cat_split))
    return jnp.transpose(leaves)       # (N, T)


def _fused_margins(X, edges, is_cat, init, feat, thresh, na_left, left,
                   right, leaf_val, cat_split, cat_table, tree_class,
                   na_bins, max_depth: int, K: int):
    """Traceable fused bin + traverse + init core: (N, F) raw float32
    features → (N,) / (N, K) margins. Shared verbatim by the jit serving
    path (_fused_score_fn) and the shard_map'd sharded-data-plane path
    (_fused_score_sharded_fn) — every op is row-local, so the two lower to
    bitwise-identical per-row programs. Binning is _bin_features (the
    BinSpec.bin_columns-bitwise core)."""
    import jax

    with jax.named_scope("bin"):
        binned = _bin_features(X, edges, is_cat, na_bins)
    acc = _forest_margins(binned, feat, thresh, na_left, left, right,
                          leaf_val, cat_split, cat_table, tree_class,
                          na_bins, max_depth, K)
    return acc + init


@functools.lru_cache(maxsize=32)
def _fused_score_fn(max_depth: int, nclasses: int, per_class: bool = False):
    """Serving fast path: binning + traversal + init margin in ONE program.

    Takes raw features as a dense (N, F) float32 matrix (categoricals as
    their integer codes, NA as NaN for numerics / negative for cats) plus
    the BinSpec tables, so the per-request host work is a single
    device_put."""
    import jax

    K = nclasses if (nclasses > 2 or per_class) else 1

    def run(X, edges, is_cat, init, feat, thresh, na_left, left, right,
            leaf_val, cat_split, cat_table, tree_class, na_bins):
        return _fused_margins(X, edges, is_cat, init, feat, thresh,
                              na_left, left, right, leaf_val, cat_split,
                              cat_table, tree_class, na_bins, max_depth, K)

    from h2o3_tpu.obs import compiles

    return compiles.ledgered_jit("tree", run, program="fused_score")


@functools.lru_cache(maxsize=32)
def _fused_score_sharded_fn(max_depth: int, nclasses: int, per_class: bool,
                            mesh):
    """Sharded-data-plane serving path: the SAME fused core, executed per
    row shard under shard_map over the named 'rows' axis (via
    compat.py). X arrives already row-sharded from
    ShardedFrame.pack_features; the forest/BinSpec tables are replicated
    (in_specs P()). Every op is per-row, so there is NO cross-shard
    communication inside the program — each process scores only its
    addressable shards, and margins come back row-sharded for the single
    gather that assembles the prediction frame."""
    import jax
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.compat import shard_map as _compat_shard_map

    K = nclasses if (nclasses > 2 or per_class) else 1

    def run(X, edges, is_cat, init, feat, thresh, na_left, left, right,
            leaf_val, cat_split, cat_table, tree_class, na_bins):
        return _fused_margins(X, edges, is_cat, init, feat, thresh,
                              na_left, left, right, leaf_val, cat_split,
                              cat_table, tree_class, na_bins, max_depth, K)

    in_specs = (P("rows", None),) + (P(),) * 13
    out_specs = P("rows", None) if K > 1 else P("rows")
    fn = _compat_shard_map(run, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs)
    from h2o3_tpu.obs import compiles

    return compiles.ledgered_jit("tree", fn, program="fused_score_sharded")


@functools.lru_cache(maxsize=8)
def _leaf_fn(max_depth: int):
    import jax

    def run(binned, feat, thresh, na_left, left, right, leaf_val,
            cat_split, cat_table, tree_class, na_bins):
        return _forest_leaves(binned, feat, thresh, na_left, left, right,
                              cat_split, cat_table, na_bins, max_depth)

    from h2o3_tpu.obs import compiles

    return compiles.ledgered_jit("tree", run, program="forest_leaves")


def _fused_leaves(X, edges, is_cat, feat, thresh, na_left, left, right,
                  cat_split, cat_table, na_bins, max_depth: int):
    """Traceable fused bin + leaf-walk core: (N, F) raw float32 features →
    (N, T) leaf node ids — the explainability twin of _fused_margins
    (leaf assignment, staged probabilities, RuleFit paths). Binning and
    walk are the SAME cores serving uses, so
    leaf = spec.bin_columns + forest.leaf_index bitwise."""
    binned = _bin_features(X, edges, is_cat, na_bins)
    return _forest_leaves(binned, feat, thresh, na_left, left, right,
                          cat_split, cat_table, na_bins, max_depth)


@functools.lru_cache(maxsize=32)
def _fused_leaf_fn(max_depth: int):
    """Explainability fast path: binning + leaf walk in ONE program over a
    bucketed (N, F) raw feature matrix (host-packed serving layout)."""
    import jax

    def run(X, edges, is_cat, feat, thresh, na_left, left, right,
            cat_split, cat_table, na_bins):
        return _fused_leaves(X, edges, is_cat, feat, thresh, na_left, left,
                             right, cat_split, cat_table, na_bins,
                             max_depth)

    from h2o3_tpu.obs import compiles

    return compiles.ledgered_jit("tree", run, program="fused_leaves")


@functools.lru_cache(maxsize=32)
def _fused_leaf_sharded_fn(max_depth: int, mesh):
    """Sharded-data-plane twin of _fused_leaf_fn: same fused core per row
    shard under shard_map over the named 'rows' axis (every op is
    row-local — no cross-shard communication; leaves come back
    row-sharded (N, T))."""
    import jax
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.compat import shard_map as _compat_shard_map

    def run(X, edges, is_cat, feat, thresh, na_left, left, right,
            cat_split, cat_table, na_bins):
        return _fused_leaves(X, edges, is_cat, feat, thresh, na_left, left,
                             right, cat_split, cat_table, na_bins,
                             max_depth)

    in_specs = (P("rows", None),) + (P(),) * 10
    fn = _compat_shard_map(run, mesh=mesh, in_specs=in_specs,
                           out_specs=P("rows", None))
    from h2o3_tpu.obs import compiles

    return compiles.ledgered_jit("tree", fn, program="fused_leaves_sharded")


def forest_predict_fn():
    """(fn, example_args) for __graft_entry__: the flagship forward step —
    a random-but-structurally-real compressed forest traversal."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    T, depth, F, B, N = 50, 5, 32, 20, 1024
    M = 2 ** (depth + 1) - 1
    feat = np.full((T, M), -1, np.int32)
    inner = M // 2
    feat[:, :inner] = rng.integers(0, F, (T, inner))
    thresh = rng.integers(0, B - 1, (T, M)).astype(np.int32)
    left = np.zeros((T, M), np.int32)
    right = np.zeros((T, M), np.int32)
    for m in range(inner):
        left[:, m], right[:, m] = 2 * m + 1, 2 * m + 2
    forest = CompressedForest(
        feat, thresh, np.zeros((T, M), bool), left, right,
        rng.standard_normal((T, M)).astype(np.float32),
        np.full((T, M), -1, np.int32), np.zeros((1, B), bool),
        np.zeros(T, np.int32), np.full(F, B - 1, np.int32), max_depth=depth)
    binned = jnp.asarray(rng.integers(0, B - 1, (N, F)), jnp.int32)

    def fwd(binned):
        return forest.predict_binned(binned)

    return fwd, (binned,)

"""Compressed forest: stacked tree arrays + vectorized device scoring.

Reference: hex/tree/CompressedTree.java — trees serialized to flat byte
arrays, scored row-at-a-time by walking the bytes (score0); genmodel
mirrors the walk for MOJOs.

TPU-native design: the forest IS a pytree of dense arrays shaped
(n_trees, max_nodes): feat / thresh_bin / na_left / left / right /
leaf_val, plus one shared categorical-subset LUT. Scoring every row
through every tree is a lax.scan over trees of a walk level by level — all
rows advance one level per step in lockstep (SIMD traversal), and step d
reads the tables of depth d alone: a row at depth d stands on one of at
most 2^d nodes. The walk reads a level-ordered view of the stored arrays
(level_view, built once a forest on the host): a tree's nodes breadth
first, each depth a contiguous run, the subsets of a depth's enum splits
contiguous too and packed 32 bins a uint32 word. Bins replace raw feature
comparisons so test data is binned once with the training edges and the
traversal is pure int compares. Row-sharded input ⇒ embarrassingly
parallel over the mesh.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import numpy as np

# the level-ordered view the walk reads lives with the standalone runner,
# which rebuilds an exported program's inputs with it (numpy only)
from h2o3_genmodel import levels
from h2o3_genmodel.levels import WALK_ARGS, level_view, walk_widths


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
    @functools.cached_property
    def _level_view(self) -> tuple:
        return level_view(vars(self), self.max_depth)

    def arrays(self):
        """What every walk program takes after the rows (WALK_ARGS): the
        level-ordered view of the stored arrays, built once a forest on
        the host, with tree_class and na_bins."""
        import jax.numpy as jnp

        return tuple(jnp.asarray(a) for a in self._level_view)

    @property
    def per_class_trees(self) -> bool:
        """True when trees are grown one-per-class (multinomial, or DRF
        binomial_double_trees — class-1 trees present at nclasses==2):
        the traversal must keep K class slots, not collapse to one."""
        return self.nclasses > 2 or (
            self.nclasses == 2
            and int(np.asarray(self.tree_class).max(initial=0)) > 0)

    @functools.cached_property
    def _walk_counts(self) -> dict:
        """What one dispatch adds to the span open at count_walk: the steps
        it walks (trees x max_depth) and how many of them read their widest
        table by gather on a TPU, by _at_node's rule from the level's
        static width: the packed subset words in a tree that takes the
        categorical branch of _walk_tree's cond (read from the view, as the
        cond reads it), the node tables in one that does not."""
        nodes, cat_words = self._level_view[:2]
        T, _, M = nodes.shape
        rows, W = cat_words.shape
        widths = walk_widths(self.max_depth, M)
        cat_trees = int((nodes[:, levels.CAT_SPLIT] >= 0).any(axis=1).sum())
        by_node = sum(table_form(w) == "gather" for w in widths)
        by_word = sum(table_form(max(w, min(w, rows) * W)) == "gather"
                      for w in widths)
        return dict(walk_levels=T * len(widths),
                    walk_gather_levels=(cat_trees * by_word
                                        + (T - cat_trees) * by_node))

    @functools.cached_property
    def walk_form(self) -> str:
        """Which form of the walk this forest's tables select (the label of
        h2o3_forest_walk_total): the row's bin is always read by select;
        `select` / `gather` says how a TPU reads the widest table of the
        walk, a level's (_at_node's rule from its static width), `+cat`
        that some tree takes the categorical branch of _walk_tree's cond."""
        nodes = self._level_view[0]
        return ("gather" if self._walk_counts["walk_gather_levels"]
                else "select") + (
            "+cat" if (nodes[:, levels.CAT_SPLIT] >= 0).any() else "")

    def count_walk(self) -> None:
        """One dispatch of a walk program: h2o3_forest_walk_total{form},
        and on the span open here (a job's `metrics`, a flush) the levels
        it walks and how many of them gather, from the static widths: host
        arithmetic, no device op."""
        from h2o3_tpu.obs import metrics, tracing

        metrics.inc("h2o3_forest_walk_total", form=self.walk_form)
        tracing.add_attrs(**self._walk_counts)

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
        """(N, T) leaf node id per tree, as stored (used by
        RuleFit/TreeSHAP/partial)."""
        fn = _leaf_fn(self.max_depth)
        self.count_walk()
        return fn(binned, *self.arrays())


# tables up to this many entries (a level's slice of a tree's node tables,
# its packed subset words, a tree's leaf values) are read by
# compare-and-select over the table axis (one fused reduce a table, cost by
# the row and the entry); wider ones (DRF from depth 13: 8,192 nodes a
# level and more) by a gather from the table (cost by the row, ~6 ns a row
# a table). Measured on a v5e, 1M rows (PERF.md §6, PR 27): select ahead 8x
# at 127 and 255 entries, 6.5x at 1,023, 1.9x at 3,997, the largest
# measured with five tables a read; the lines would cross near 8,000. One
# table of 5,120 words reads 4 ns a row faster by select (PR 29, PR 33):
# whoever moves the rule measures five tables at 8,192 too
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


def _step(node, start, tables, words, binned, na_bins):
    """One level of the lockstep walk: every row moves from `node` to its
    child (a leaf stays, at this depth or above it). `tables` = this
    level's slice of the tree's FEAT, THRESH, NA_LEFT, LEFT rows and, in a
    tree with an enum split, CAT_SPLIT, from position `start`; `words` its
    slice of cat_words, or None: the categorical lookup is traced only into
    the walk of a tree that has one. The subset test is
    device_tree._route's: bit b & 31 of the word at row * W + (b >> 5),
    read through _at_node like the node tables. The ONE step margins, leaf
    ids and everything built on them share. No operand of a gather here
    carries the row axis, and none is two-dimensional."""
    import jax.numpy as jnp

    local = node - start
    at = _at_node(tables, local)
    f, t, na_goes_left, lft = at[:4]
    b, is_na = _bin_at(binned, jnp.maximum(f, 0), na_bins)
    go_left = b <= t
    if words is not None:
        csid = at[4]
        W = words.shape[1]
        bw = jnp.minimum(b, 32 * W - 1)
        word, = _at_node((words.reshape(-1),),
                         jnp.maximum(csid, 0) * W + (bw >> 5))
        cat_left = (word >> (bw & 31).astype(jnp.uint32)) & 1 == 1
        go_left = jnp.where(csid >= 0, cat_left, go_left)
    go_left = jnp.where(is_na, na_goes_left != 0, go_left)
    return jnp.where((local < 0) | (f < 0), node,
                     jnp.where(go_left, lft, lft + 1))


def _walk_tree(binned, nodes, starts, cat_words, na_bins, max_depth: int):
    """(N,) position each row ends in after max_depth steps of one tree
    (its (7, M) `nodes` and (2, max_depth) `starts` of level_view; at depth
    max_depth every node is a leaf, so no step reads it). Step d reads
    walk_widths' W_d entries of the node rows from LEVEL_START[d]; the
    levels whose width has reached M have one shape and share one
    fori_loop. Which of the two step forms runs is read from the tree
    itself, on the device: a tree with no categorical split never executes
    the lookup it would only discard. A forest that reaches no enum split
    at all has no row in cat_words, a static shape: its program holds the
    numeric walk alone (half the trace, no cond)."""
    import jax
    import jax.numpy as jnp

    M = nodes.shape[1]
    widths = walk_widths(max_depth, M)
    narrow = sum(w < M for w in widths)

    def walk(with_cat: bool):
        read = levels.CAT_SPLIT + 1 if with_cat else levels.LEFT + 1

        def level(d, width, node):
            start = starts[levels.LEVEL_START, d]
            tables = jax.lax.dynamic_slice(nodes, (0, start), (read, width))
            words = jax.lax.dynamic_slice_in_dim(
                cat_words, starts[levels.CAT_START, d],
                min(width, cat_words.shape[0])) if with_cat else None
            return _step(node, start, tuple(tables), words, binned, na_bins)

        def run(node):
            for d in range(narrow):
                node = level(d, widths[d], node)
            if narrow < max_depth:
                node = jax.lax.fori_loop(
                    narrow, max_depth, lambda d, n: level(d, M, n), node)
            return node
        return run

    # the carry is derived from `binned` so it carries its type: under
    # shard_map the rows vary over the mesh axis and a fresh jnp.zeros
    # would not, which the loop carry check rejects; under plain jit this
    # is the same zeros
    node0 = jnp.zeros_like(binned[:, 0], dtype=jnp.int32)
    if cat_words.shape[0] == 0:
        return walk(False)(node0)
    # the barrier keeps what reads the position out of the cond: the TPU
    # compiler otherwise moves a select's broadcast of it over a table's M
    # entries into both branches, whose output is then (N, M) in memory
    return jax.lax.optimization_barrier(jax.lax.cond(
        jnp.any(nodes[levels.CAT_SPLIT] >= 0), walk(True), walk(False),
        node0))


def _forest_margins(binned, nodes, cat_words, tree_class, na_bins, starts,
                    max_depth: int, K: int):
    """Traceable core of the lockstep traversal: (N, F) integer bins →
    (N,) / (N, K) leaf-value sums (the tables: WALK_ARGS). Shared verbatim
    by the per-request traversal (_traverse_fn) and the serving fast
    path's fused program (_fused_score_fn) so both produce
    bitwise-identical margins."""
    import jax
    import jax.numpy as jnp

    N = binned.shape[0]

    def walk_one_tree(acc, tree):
        tnodes, tstarts, tcls = tree
        node = _walk_tree(binned, tnodes, tstarts, cat_words, na_bins,
                          max_depth)
        # the leaf's value by _at_node's rule too, as its bits: a sum over
        # floats would turn a leaf of -0.0 into 0.0
        bits, = _at_node((tnodes[levels.LEAF_BITS],), node)
        contrib = jax.lax.bitcast_convert_type(bits, jnp.float32)
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
        acc, _ = jax.lax.scan(walk_one_tree, acc0,
                              (nodes, starts, tree_class))
    return acc


@functools.lru_cache(maxsize=32)
def _traverse_fn(max_depth: int, nclasses: int, per_class: bool = False):
    K = nclasses if (nclasses > 2 or per_class) else 1

    def run(binned, *forest):
        return _forest_margins(binned, *forest, max_depth, K)

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


def _forest_leaves(binned, nodes, cat_words, tree_class, na_bins, starts,
                   max_depth: int):
    """Traceable leaf-walk core: (N, F) integer bins → (N, T) leaf node
    ids, the stored ones (one more _at_node read a tree, of STORED_ID at
    the position the walk ends in). The walk is _forest_margins' own
    (_walk_tree), so the leaf a row lands in is by construction the leaf
    whose value the margin summed — shared by the per-request _leaf_fn and
    the fused leaf programs."""
    import jax
    import jax.numpy as jnp

    def walk(carry, tree):
        tnodes, tstarts = tree
        node = _walk_tree(binned, tnodes, tstarts, cat_words, na_bins,
                          max_depth)
        return carry, _at_node((tnodes[levels.STORED_ID],), node)[0]

    _, leaves = jax.lax.scan(walk, None, (nodes, starts))
    return jnp.transpose(leaves)       # (N, T)


def _fused_margins(X, edges, is_cat, init, *forest_depth_k):
    """Traceable fused bin + traverse + init core: (N, F) raw float32
    features → (N,) / (N, K) margins; after `init` the WALK_ARGS tables,
    then max_depth and K. Shared verbatim by the jit serving path
    (_fused_score_fn) and the shard_map'd sharded-data-plane path
    (_fused_score_sharded_fn) — every op is row-local, so the two lower to
    bitwise-identical per-row programs. Binning is _bin_features (the
    BinSpec.bin_columns-bitwise core)."""
    import jax

    *forest, max_depth, K = forest_depth_k
    with jax.named_scope("bin"):
        binned = _bin_features(X, edges, is_cat,
                               forest[WALK_ARGS.index("na_bins")])
    return _forest_margins(binned, *forest, max_depth, K) + init


@functools.lru_cache(maxsize=32)
def _fused_score_fn(max_depth: int, nclasses: int, per_class: bool = False):
    """Serving fast path: binning + traversal + init margin in ONE program.

    Takes raw features as a dense (N, F) float32 matrix (categoricals as
    their integer codes, NA as NaN for numerics / negative for cats) plus
    the BinSpec tables, so the per-request host work is a single
    device_put."""
    K = nclasses if (nclasses > 2 or per_class) else 1

    def run(X, edges, is_cat, init, *forest):
        return _fused_margins(X, edges, is_cat, init, *forest, max_depth, K)

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
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.compat import shard_map as _compat_shard_map

    K = nclasses if (nclasses > 2 or per_class) else 1

    def run(X, edges, is_cat, init, *forest):
        return _fused_margins(X, edges, is_cat, init, *forest, max_depth, K)

    in_specs = (P("rows", None),) + (P(),) * (3 + len(WALK_ARGS))
    out_specs = P("rows", None) if K > 1 else P("rows")
    fn = _compat_shard_map(run, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs)
    from h2o3_tpu.obs import compiles

    return compiles.ledgered_jit("tree", fn, program="fused_score_sharded")


@functools.lru_cache(maxsize=8)
def _leaf_fn(max_depth: int):
    def run(binned, *forest):
        return _forest_leaves(binned, *forest, max_depth)

    from h2o3_tpu.obs import compiles

    return compiles.ledgered_jit("tree", run, program="forest_leaves")


def _fused_leaves(X, edges, is_cat, *forest_depth):
    """Traceable fused bin + leaf-walk core: (N, F) raw float32 features →
    (N, T) leaf node ids — the explainability twin of _fused_margins
    (leaf assignment, staged probabilities, RuleFit paths). Binning and
    walk are the SAME cores serving uses, so
    leaf = spec.bin_columns + forest.leaf_index bitwise."""
    *forest, max_depth = forest_depth
    binned = _bin_features(X, edges, is_cat,
                           forest[WALK_ARGS.index("na_bins")])
    return _forest_leaves(binned, *forest, max_depth)


@functools.lru_cache(maxsize=32)
def _fused_leaf_fn(max_depth: int):
    """Explainability fast path: binning + leaf walk in ONE program over a
    bucketed (N, F) raw feature matrix (host-packed serving layout)."""
    def run(X, edges, is_cat, *forest):
        return _fused_leaves(X, edges, is_cat, *forest, max_depth)

    from h2o3_tpu.obs import compiles

    return compiles.ledgered_jit("tree", run, program="fused_leaves")


@functools.lru_cache(maxsize=32)
def _fused_leaf_sharded_fn(max_depth: int, mesh):
    """Sharded-data-plane twin of _fused_leaf_fn: same fused core per row
    shard under shard_map over the named 'rows' axis (every op is
    row-local — no cross-shard communication; leaves come back
    row-sharded (N, T))."""
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.compat import shard_map as _compat_shard_map

    def run(X, edges, is_cat, *forest):
        return _fused_leaves(X, edges, is_cat, *forest, max_depth)

    in_specs = (P("rows", None),) + (P(),) * (2 + len(WALK_ARGS))
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

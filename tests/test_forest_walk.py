"""The forest walk (ISSUE 27, level by level since ISSUE 33): margins and
leaf ids of the device traversal are bitwise those of a row-at-a-time walk
on the host over the stored arrays, over every kind of forest that runs the
one step (`compressed._step`); step d reads depth d's entries alone, the
enum subset as one bit of a packed word; the step gathers from no operand
that carries the rows; `h2o3_forest_walk_total` says which form ran.

Tiny frames on the CPU mesh: what is asserted is equality and structure,
never a time."""

import numpy as np
import pytest

from h2o3_tpu.models.tree import compressed
from h2o3_tpu.models.tree.compressed import CompressedForest
from h2o3_tpu.obs import metrics

N = 236          # rows; no table of any case below has this many entries


def _forest(seed, F, na_bins, depth, *, split_p=1.0, cat_feats=(), T=5,
            K=1, order="random", leaf_at_2=False):
    """A random forest as CompressedForest holds it. Node ids are handed
    out in the order nodes are reached (`order`: at random, depth first, or
    a level at a time as tree_program stores them), so left/right are not
    2m+1/2m+2; `split_p` < 1 leaves branches short (uneven trees);
    `leaf_at_2` makes the first node of depth 2 a leaf; a split on a
    feature in `cat_feats` is a categorical subset over all of its bins."""
    rng = np.random.default_rng(seed)
    maxB = int(na_bins.max()) + 1
    trees, cat_rows = [], []
    for _ in range(T):
        nodes = [dict(depth=0)]
        todo = [0]
        early = leaf_at_2
        while todo:
            m = todo.pop({"random": rng.integers(len(todo)), "dfs": -1,
                          "level": 0}[order])
            nd = nodes[m]
            if early and nd["depth"] == 2:
                early = False
                nd["leaf"] = np.float32(rng.standard_normal())
                continue
            if nd["depth"] >= depth or (m and rng.random() > split_p):
                nd["leaf"] = np.float32(rng.standard_normal())
                continue
            f = int(rng.integers(F))
            nd.update(feat=f, na_left=bool(rng.integers(2)),
                      thresh=int(rng.integers(max(int(na_bins[f]), 1))))
            if f in cat_feats:
                row = np.zeros(maxB, bool)
                row[: na_bins[f] + 1] = rng.integers(0, 2, na_bins[f] + 1)
                nd["cat"] = len(cat_rows)
                cat_rows.append(row)
            for side in ("left", "right"):
                nd[side] = len(nodes)
                nodes.append(dict(depth=nd["depth"] + 1))
                todo.append(nd[side])
        trees.append(nodes)
    M = max(len(t) for t in trees)
    feat = np.full((T, M), -1, np.int32)
    cat_split = np.full((T, M), -1, np.int32)
    thresh, left, right = (np.zeros((T, M), np.int32) for _ in range(3))
    na_left = np.zeros((T, M), bool)
    leaf_val = np.zeros((T, M), np.float32)
    for t, nodes in enumerate(trees):
        for m, nd in enumerate(nodes):
            if "leaf" in nd:
                leaf_val[t, m] = nd["leaf"]
                continue
            feat[t, m], thresh[t, m] = nd["feat"], nd["thresh"]
            na_left[t, m] = nd["na_left"]
            left[t, m], right[t, m] = nd["left"], nd["right"]
            cat_split[t, m] = nd.get("cat", -1)
    cat_table = (np.stack(cat_rows) if cat_rows
                 else np.zeros((1, maxB), bool))
    tree_class = (np.arange(T) % K).astype(np.int32)
    return CompressedForest(feat, thresh, na_left, left, right, leaf_val,
                            cat_split, cat_table, tree_class,
                            na_bins.astype(np.int32), max_depth=depth,
                            nclasses=K)


def _host_walk(fo, binned):
    """Row at a time, tree by tree: (margins, (N, T) leaf ids). The margin
    is the f32 sum of leaf values in tree order, as the scan adds them."""
    n, T = binned.shape[0], fo.n_trees
    K = fo.nclasses if fo.per_class_trees else 1
    acc = np.zeros((n, K), np.float32)
    leaves = np.zeros((n, T), np.int32)
    maxB = fo.cat_table.shape[1]
    for t in range(T):
        for i in range(n):
            node = 0
            for _ in range(fo.max_depth + 1):
                f = fo.feat[t, node]
                if f < 0:
                    break
                b = int(binned[i, f])
                if b == fo.na_bins[f]:
                    go_left = fo.na_left[t, node]
                elif fo.cat_split[t, node] >= 0:
                    go_left = fo.cat_table[fo.cat_split[t, node],
                                           min(b, maxB - 1)]
                else:
                    go_left = b <= fo.thresh_bin[t, node]
                node = fo.left[t, node] if go_left else fo.right[t, node]
            leaves[i, t] = node
            acc[i, fo.tree_class[t] if K > 1 else 0] += fo.leaf_val[t, node]
    return (acc if K > 1 else acc[:, 0]), leaves


def _bins(seed, na_bins, dtype, n=N):
    """Every bin of every feature, its NA bin included."""
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, nb + 1, n) for nb in na_bins],
                    axis=1).astype(dtype)


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and \
        a.tobytes() == b.tobytes()


AIRLINE_NA = np.array([12, 31, 7, 22, 300, 300, 100, 100])


def _after_concat(seed):
    """A categorical forest with a numeric one of another width appended
    (checkpoint continuation): b's subset rows shift, the tables pad."""
    a = _forest(seed, F=4, na_bins=np.array([20, 300, 20, 40]), depth=4,
                cat_feats=(1, 3), T=3, split_p=.8)
    b = _forest(seed + 1, F=4, na_bins=np.array([20, 300, 20, 40]), depth=6,
                cat_feats=(3,), T=4, split_p=.9)
    return CompressedForest.concat(a, b)


# name -> (forest kwargs or a maker, dtype of the bin matrix, the form it
# must count as)
CASES = {
    "higgs_d5_u8": (dict(F=28, na_bins=np.full(28, 20), depth=5), np.uint8,
                    "select"),
    "higgs_d5_int32": (dict(F=28, na_bins=np.full(28, 20), depth=5),
                       np.int32, "select"),
    "na_both_ways": (dict(F=4, na_bins=np.array([1, 2, 3, 2]), depth=4,
                          T=8), np.uint8, "select"),
    "cat_bins_above_255": (dict(F=3, na_bins=np.array([1024, 20, 700]),
                                depth=4, cat_feats=(0, 2)), np.int16,
                           "select+cat"),
    "cat_and_numeric_trees": (dict(F=6, na_bins=np.array([20] * 5 + [300]),
                                   depth=3, cat_feats=(5,), T=12, split_p=.7),
                              np.int16, "select+cat"),
    "multinomial_k3": (dict(F=5, na_bins=np.full(5, 16), depth=3, T=9, K=3),
                       np.uint8, "select"),
    "uneven_d9_hundreds_of_nodes": (dict(F=7, na_bins=np.full(7, 20),
                                         depth=9, split_p=.9, T=4),
                                    np.uint8, "select"),
    # no level above depth 13 is wider than 4,096: the whole tree is
    "deep_uneven_d13": (dict(F=7, na_bins=np.full(7, 20), depth=13,
                             split_p=.97, T=3), np.uint8, "select"),
    "deep_uneven_d14": (dict(F=7, na_bins=np.full(7, 20), depth=14,
                             split_p=.97, T=2), np.uint8, "gather"),
    "one_feature": (dict(F=1, na_bins=np.array([20]), depth=3), np.uint8,
                    "select"),
    "f300": (dict(F=300, na_bins=np.full(300, 20), depth=5), np.uint8,
             "select"),
    # ISSUE 33: what the level view has to get right
    "stored_depth_first": (dict(F=6, na_bins=np.array([20] * 5 + [300]),
                                depth=6, cat_feats=(5,), T=4, split_p=.85,
                                order="dfs"), np.int16, "select+cat"),
    "stored_level_order": (dict(F=6, na_bins=np.array([20] * 5 + [300]),
                                depth=6, cat_feats=(5,), T=4, split_p=.85,
                                order="level"), np.int16, "select+cat"),
    "leaf_at_depth_2": (dict(F=5, na_bins=np.full(5, 20), depth=7, T=3,
                             leaf_at_2=True), np.uint8, "select"),
    "saturated_widths": (dict(F=5, na_bins=np.array([20, 20, 300, 20, 20]),
                              depth=11, cat_feats=(2,), T=4, split_p=.62),
                         np.int16, "select+cat"),
    "after_concat": (_after_concat, np.int16, "select+cat"),
    "airline_int16_301_bins": (dict(F=8, na_bins=AIRLINE_NA, depth=7,
                                    cat_feats=range(6), T=2, split_p=.95),
                               np.int16, "select+cat"),
    "cat_1025_bins_d8": (dict(F=3, na_bins=np.array([1024, 20, 700]),
                              depth=8, cat_feats=(0, 2), T=3, split_p=.93),
                         np.int16, "gather+cat"),
    "multinomial_k3_cat": (dict(F=5, na_bins=np.array([16, 16, 40, 16, 16]),
                                depth=5, cat_feats=(2,), T=9, K=3,
                                split_p=.8), np.uint8, "select+cat"),
}


def _case(name):
    """(forest, bins of every feature with its NA bin, dtype, form)."""
    kw, dtype, form = CASES[name]
    seed = sum(map(ord, name))
    fo = kw(seed) if callable(kw) else _forest(seed, **kw)
    return fo, _bins(1, np.asarray(fo.na_bins), dtype), form


@pytest.mark.parametrize("name", sorted(CASES))
def test_walk_is_bitwise_the_host_walk(name):
    fo, binned, form = _case(name)
    want_margin, want_leaves = _host_walk(fo, binned)
    assert fo.walk_form == form
    M = fo.feat.shape[1]
    if name == "uneven_d9_hundreds_of_nodes":
        assert 200 < M <= compressed._SELECT_MAX_NODES
    if name in ("deep_uneven_d13", "deep_uneven_d14"):
        # the rule is a level's now: both trees are wider than it, one has
        # a level that is
        assert M > compressed._SELECT_MAX_NODES
        assert (max(compressed.walk_widths(fo.max_depth, M))
                > compressed._SELECT_MAX_NODES) == (form == "gather")
    if name == "cat_and_numeric_trees":      # both sides of the cond
        has_cat = (fo.cat_split >= 0).any(axis=1)
        assert has_cat.any() and not has_cat.all()
    nodes = fo._level_view[compressed.WALK_ARGS.index("nodes")]
    identity = (nodes[:, compressed.levels.STORED_ID] == np.arange(M)).all()
    if name == "stored_depth_first":
        assert not identity
    if name == "stored_level_order":
        assert identity
    if name == "saturated_widths":           # levels that share the loop
        assert M < 2 ** (fo.max_depth - 2)
    if name == "leaf_at_depth_2":
        assert (want_leaves.min(axis=0) < 7).all()    # rows stop there ...
        assert fo.max_depth == 7 and M > 2 ** 6       # ... beside full paths
    if name == "after_concat":
        assert fo.n_trees == 7 and (fo.cat_split[3:] >= 0).any()
    assert _same_bits(fo.predict_binned(binned), want_margin)
    assert _same_bits(fo.leaf_index(binned), want_leaves)


SELECT_CASES = ["higgs_d5_u8", "cat_and_numeric_trees",
                "uneven_d9_hundreds_of_nodes", "stored_depth_first",
                "saturated_widths", "after_concat",
                "airline_int16_301_bins", "multinomial_k3_cat"]


@pytest.mark.parametrize("name", SELECT_CASES)
def test_tables_by_select_walk_like_the_host(name, monkeypatch):
    """The TPU's form of the table lookups (XLA:CPU lowers the gather form:
    _at_node), run here on the CPU in the CPU form's place, in fresh
    programs: the same bits."""
    import jax

    monkeypatch.setattr(compressed, "_tables_by_gather",
                        compressed._tables_by_select)
    fo, binned, _ = _case(name)
    K = fo.nclasses if fo.per_class_trees else 1
    want_margin, want_leaves = _host_walk(fo, binned)
    a = fo.arrays()
    jaxpr = jax.make_jaxpr(lambda b, *a: compressed._forest_margins(
        b, *a, fo.max_depth, K))(binned, *a).jaxpr
    assert not [e.invars[0].aval for e in _eqns(jaxpr)      # the leaf's
                if e.primitive.name == "gather"]            # value too
    got = jax.jit(lambda b, *a: compressed._forest_margins(
        b, *a, fo.max_depth, K))(binned, *a)
    assert _same_bits(got, want_margin)
    got = jax.jit(lambda b, *a: compressed._forest_leaves(
        b, *a, fo.max_depth))(binned, *a)
    assert _same_bits(got, want_leaves)


@pytest.mark.parametrize("program,cat", [("margins", True), ("leaves", True),
                                         ("margins", False)])
def test_step_d_compares_with_depth_d_alone(program, cat, monkeypatch):
    """The structure ISSUE 33's speed rests on, in the TPU form's jaxpr: a
    compare over a table axis is as wide as the level (min(2^d, M) entries
    of the node tables, as many rows of W packed words or all there are),
    once a step in
    each branch of the cond, never the whole tree's M; and no gather reads
    a two-dimensional table (the (C, maxB) cat_table is gone from the
    program). So the walk cannot silently go back to whole-tree tables. A
    forest that reaches no enum split has no row of words, and its program
    neither the cond nor its second branch: one trace of a step a level."""
    import collections

    import jax

    monkeypatch.setattr(compressed, "_tables_by_gather",
                        compressed._tables_by_select)
    depth, F = 6, 7
    fo = _forest(9, F=F, na_bins=np.array([20] * 6 + [300]), depth=depth,
                 cat_feats=(6,) if cat else (), T=3)
    M = fo.feat.shape[1]
    C, W = fo._level_view[compressed.WALK_ARGS.index("cat_words")].shape
    assert M == 2 ** (depth + 1) - 1 and W == 10       # full trees
    assert (C > 0) == cat
    binned = _bins(4, np.asarray(fo.na_bins), np.int16)
    fn = ((lambda b, *a: compressed._forest_margins(b, *a, depth, 1))
          if program == "margins" else
          (lambda b, *a: compressed._forest_leaves(b, *a, depth)))
    eqns = list(_eqns(jax.make_jaxpr(fn)(binned, *fo.arrays()).jaxpr))
    branches = 2 if cat else 1
    widths = collections.Counter(
        e.outvars[0].aval.shape[1] for e in eqns
        if e.primitive.name == "eq" and e.outvars[0].aval.ndim == 2
        and e.outvars[0].aval.shape[0] == N)
    bin_reads = widths.pop(F)            # _bin_at: the hit mask and the NA
    assert bin_reads == 2 * branches * depth     # test, a step, a branch
    # _at_node traces its two platform forms, and both are the select here
    want = collections.Counter()
    for d in range(depth):
        want[2 ** d] += 2 * branches     # node tables
        if cat:
            want[min(2 ** d, C) * W] += 2    # packed words: the cat branch
    want[M] += 2         # once a tree: the leaf's value, or its stored id
    assert widths == want
    assert not [e.invars[0].aval for e in eqns if e.primitive.name ==
                "gather" and e.invars[0].aval.ndim != 1]


def _host_bin(X, edges, is_cat, na_bins):
    """BinSpec's binning on the host: #edges < x, the code of a
    categorical, NaN and out-of-range codes to the NA bin."""
    out = np.empty(X.shape, np.int32)
    for f in range(X.shape[1]):
        x = X[:, f]
        if is_cat[f]:
            c = np.where(np.isnan(x), -1, x).astype(np.int32)
            out[:, f] = np.where((c < 0) | (c >= na_bins[f]), na_bins[f], c)
        else:
            out[:, f] = np.where(np.isnan(x), na_bins[f],
                                 (edges[f][None, :] < x[:, None]).sum(1))
    return out


@pytest.mark.parametrize("K", [1, 3])
def test_shard_map_programs_walk_like_the_host(K):
    """The four-chip serving path: the fused programs under shard_map over
    `rows`, on four CPU devices. Carries are typed from the rows; the
    cond's predicate is replicated and its branches vary."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    F, n = 5, 4 * 59
    na_bins = np.array([20, 20, 40, 20, 6])
    is_cat = np.array([False, False, True, False, True])
    fo = _forest(11 + K, F, na_bins, depth=4, cat_feats=(2,), T=6, K=K,
                 split_p=.8)
    rng = np.random.default_rng(5)
    edges = np.full((F, 19), np.inf, np.float32)
    edges[~is_cat] = np.sort(rng.standard_normal((3, 19)), 1)
    X = rng.standard_normal((n, F)).astype(np.float32)
    X[:, is_cat] = rng.integers(-1, 45, (n, 2))
    X[rng.random((n, F)) < .1] = np.nan
    want_margin, want_leaves = _host_walk(
        fo, _host_bin(X, edges, is_cat, na_bins))

    mesh = Mesh(np.array(jax.devices()[:4]), ("rows",))
    Xd = jax.device_put(X, NamedSharding(mesh, P("rows", None)))
    tables = (jnp.asarray(edges), jnp.asarray(is_cat))
    arrays = fo.arrays()
    score = compressed._fused_score_sharded_fn(
        fo.max_depth, fo.nclasses, fo.per_class_trees, mesh)
    got = score(Xd, *tables, jnp.float32(0.0), *arrays)
    assert len(got.sharding.device_set) == 4
    assert _same_bits(got, want_margin)
    leaf = compressed._fused_leaf_sharded_fn(fo.max_depth, mesh)
    got = leaf(Xd, *tables, *arrays)
    assert _same_bits(got, want_leaves)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("program", ["margins", "leaves"])
def test_no_gather_operand_carries_the_rows(program):
    """The structure the speed rests on: in the traversal of a numeric
    forest no `gather` reads from an array whose leading dimension is the
    row count (a per-row gather has no hardware on a TPU). What XLA:CPU
    gathers from is a level's slice of a (M,) table, the packed words and
    the leaf values."""
    import jax

    kw, dtype, _ = CASES["higgs_d5_u8"]
    fo = _forest(3, **kw)
    binned = _bins(2, kw["na_bins"], dtype)
    fn = (compressed._traverse_fn(fo.max_depth, fo.nclasses, False)
          if program == "margins" else compressed._leaf_fn(fo.max_depth))
    eqns = list(_eqns(jax.make_jaxpr(fn)(binned, *fo.arrays()).jaxpr))
    names = {e.primitive.name for e in eqns}
    assert {"scan", "cond"} <= names
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    assert gathers                       # the CPU form of _at_node
    assert all(e.invars[0].aval.shape[0] != N for e in gathers), \
        [e.invars[0].aval for e in gathers]


def _walks():
    return {s["labels"].get("form"): s["value"] for s in
            metrics.REGISTRY.get("h2o3_forest_walk_total").snapshot()[
                "samples"] if s["labels"]}


def _delta(before):
    now = _walks()
    return {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}


def test_walk_counter_names_the_form():
    """`select` | `gather` is the widest read of the walk, a level's:
    4,096 nodes at depth 12 are selected, 8,192 at depth 13 gathered, and
    so are 128 subset rows of 33 words."""
    numeric, bn, _ = _case("higgs_d5_u8")
    cat, bc, _ = _case("cat_bins_above_255")
    deep, bd, _ = _case("deep_uneven_d14")
    wide, bw, _ = _case("cat_1025_bins_d8")
    before = _walks()
    numeric.predict_binned(bn[:16])
    numeric.leaf_index(bn[:16])
    cat.predict_binned(bc[:16])
    deep.leaf_index(bd[:16])
    wide.predict_binned(bw[:16])
    assert _delta(before) == {"select": 2, "select+cat": 1, "gather": 1,
                              "gather+cat": 1}
    # what count_walk gives the open span: trees x levels, from widths
    assert deep._walk_counts == dict(walk_levels=2 * 14,
                                     walk_gather_levels=2)      # depth 13
    assert wide._walk_counts == dict(walk_levels=3 * 8,
                                     walk_gather_levels=3)      # depth 7
    assert numeric._walk_counts["walk_gather_levels"] == 0


def test_walk_counter_counts_session_dispatches_and_adds_none(cl):
    """One count a dispatch of the scoring session's programs, under the
    served forest's form; the dispatch and compile counters that
    tests/test_trace_tree.py holds read what they read without it."""
    from h2o3_tpu import scoring
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.models.tree.gbm import GBM

    def frame(n, seed, response=True):
        rng = np.random.default_rng(seed)
        fr = Frame()
        x1, x2 = rng.standard_normal(n), rng.standard_normal(n)
        fr.add("x1", Column.from_numpy(x1))
        fr.add("x2", Column.from_numpy(x2))
        if response:
            fr.add("y", Column.from_numpy(np.where(
                rng.random(n) < 1 / (1 + np.exp(x2 - x1)), "Y", "N"),
                ctype="enum"))
        return fr

    model = GBM(ntrees=3, max_depth=3, seed=7).train(
        y="y", training_frame=frame(900, 1))
    assert model.forest.walk_form == "select"
    sess = scoring.session_for(model)
    fr = frame(500, 2, response=False)
    sess.predict(fr)                                   # warm the bucket

    def counter(name):
        return sum(s["value"] for s in
                   metrics.REGISTRY.get(name).snapshot()["samples"])

    walks, compiles = _walks(), counter("h2o3_backend_compiles_total")
    traversal_compiles = sess.traversal_compiles
    dispatches = scoring.dispatch_counters()
    scoring.score_request(model, fr, with_metrics=True)
    after = scoring.dispatch_counters()
    n_dispatch = sum(after.values()) - sum(dispatches.values())
    assert n_dispatch > 0
    assert _delta(walks) == {"select": n_dispatch}
    assert sess.traversal_compiles == traversal_compiles
    assert counter("h2o3_backend_compiles_total") == compiles


@pytest.fixture(scope="module")
def one_chip():
    """One described (not attached) v5e device: the TPU compiler runs here
    with no chip. Made inside a fixture, never at import (one process at a
    time may load libtpu; every xdist worker imports this file)."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler, nothing to hold
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# rows, F, bins' dtype, T, M, subset rows, maxB, depth, scratch bytes a row.
# No subset row is a forest that reaches no enum split (the cell's own
# program: the numeric walk alone); the depth-20 shape has one, so that both
# branches of the cond hold the loop over its saturated levels. The scratch
# bound of a shape is what it measured compiled for a v5e (24.1, 36.4 and
# 100.2 B a row: PERF.md §6, PR 33; the parent 33 at the first) with less
# room above it than one more (N, F) copy of the bins in their own dtype
# takes (28, 16, 28 B a row).
CHIP_SHAPES = {
    "higgs_gbm_d5": (16_000_000, 28, "uint8", 5, 63, 0, 21, 5, 40),
    "airline_gbm_d10": (16_000_000, 8, "int16", 2, 2047, 2000, 301, 10, 48),
    "drf_d20_m20000": (16_000_000, 28, "uint8", 5, 20_000, 1, 21, 20, 112),
}


@pytest.mark.parametrize("shape", sorted(CHIP_SHAPES))
def test_training_metrics_walk_compiles_for_the_chip_without_a_row_gather(
        shape, one_chip):
    """The training-metrics traversal at real shapes, compiled for a v5e:
    `gbm_train`'s (16M x 28 u8 bins, 5 trees of 63 nodes),
    `airline_gbm_d10`'s (16M x 8 int16, 2 trees of 2,047 nodes, 2,000
    subsets of 301 bins) and a depth-20 forest of 20,000 nodes a tree (15
    unrolled levels and one loop over the five that read all M). The select
    over the feature axis fuses into reduces over the bin matrix as it
    lies (no (N, F), (N, W_d) or (N, M) intermediate: the scratch is the
    loop carries and a few (N,) vectors, held to each shape's own bound in
    CHIP_SHAPES), and no gather reads the rows or a two-dimensional table.
    Compile seconds and scratch are printed (PERF.md §6 holds them beside
    the parent's: compiler work on this sandbox's CPU)."""
    import re
    import time

    import jax
    import jax.numpy as jnp

    from h2o3_tpu.models.tree import device_tree

    rows, F, dtype, T, M, C, maxB, depth, scratch_row = CHIP_SHAPES[shape]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = jnp.int32
    forest = dict(
        nodes=arg((T, 7, M), i32),
        cat_words=arg((C, device_tree.route_words(maxB)), jnp.uint32),
        tree_class=arg((T,), i32), na_bins=arg((F,), i32),
        starts=arg((T, 2, depth), i32))
    t0 = time.perf_counter()
    lowered = compressed._traverse_fn(depth, 2).lower(
        arg((rows, F), jnp.dtype(dtype)),
        *(forest[k] for k in compressed.WALK_ARGS))
    compiled = lowered.compile()
    scratch = compiled.memory_analysis().temp_size_in_bytes
    print(f"\n{shape}: walk compiled for a v5e in "
          f"{time.perf_counter() - t0:.1f} s on this CPU, "
          f"scratch {scratch / rows:.1f} B a row")
    assert scratch < scratch_row * rows
    # lowered for a TPU the level's tables and the leaf's value are
    # selected while the table is narrow: what is left to gather are the
    # levels past _SELECT_MAX_NODES entries (airline: depth 9's 5,120
    # words; depth 20: 8,192, 16,384 and 20,000 nodes, four tables in one
    # branch and cat_split beside them in the other, and the leaf values)
    gathers = re.findall(r"stablehlo\.gather.*?:\s*\(tensor<([^>]*)>",
                         lowered.as_text())
    assert len(gathers) == {"higgs_gbm_d5": 0, "airline_gbm_d10": 1,
                            "drf_d20_m20000": 1 + (4 + 5) * 3}[shape]
    assert all("x" not in g.split("x", 1)[1] for g in gathers), gathers
    text = compiled.as_text()
    assert "reduce(" in text
    shapes = dict(re.findall(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]", text, re.M))
    assert not [shapes[op] for op in re.findall(
        r" gather\((%[\w.\-]+),", text)
        if shapes[op].split(",")[0] == str(rows)]


@pytest.mark.parametrize("config", ["higgs_gbm_d5", "airline_gbm_d10"])
def test_tree_program_lowers_for_the_chip_without_a_row_gather(config,
                                                               one_chip):
    """The twin of the test above for training (ISSUE 29): `jit_tree_program`
    lowered for a v5e at both configurations' real shapes holds no gather
    whose operand carries the 16M rows (what is gathered: histogram and
    search tables, and level 9's 5,120 packed words in `airline_gbm_d10`);
    and the route step alone, compiled for the chip at the widest level of
    either form, keeps its scratch to a few (N,) vectors: no (N, S),
    (N, S x W) or (N, F) intermediate, and again no row gather."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.models.tree import device_tree

    rows = 16_000_000
    depth, nbins, is_cat, dtype, levels = {
        "higgs_gbm_d5": (5, (21,) * 28, (False,) * 28, jnp.uint8, (16,)),
        "airline_gbm_d10": (10, (13, 32, 8, 23, 301, 301, 101, 101),
                            (True,) * 6 + (False,) * 2, jnp.int16,
                            (256, 512)),
    }[config]
    F, maxB = len(nbins), max(nbins)
    mesh = Mesh(np.array(list(one_chip.device_set)), ("rows",))

    def sharded(shape, dt, *axes):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, P(*axes)))

    grow = device_tree._grow_fn(
        depth, F, maxB, nbins, is_cat, 10.0, 1e-5, False, mesh, rows,
        device_tree._pick_blk(rows, sum(nbins)),
        device_tree.frontier_cap(F, maxB))
    f32 = sharded((rows,), jnp.float32, "rows")
    text = grow.lower(sharded((rows, F), dtype, "rows", None), f32, f32, f32,
                      f32, np.zeros(0, np.float32)).as_text()
    assert "module @jit_tree_program" in text
    gathered = re.findall(r"stablehlo\.gather.*?:\s*\(tensor<([^>]*)>", text)
    # the program pads the rows to whole histogram blocks: nothing gathered
    # from has a leading dimension anywhere near them
    assert gathered and max(int(t.split("x")[0]) for t in gathered) < 10 ** 5
    assert (f"{512 * 10}xui32" in gathered) == (config == "airline_gbm_d10")

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    i32 = jnp.int32
    for S in levels:
        compiled = jax.jit(lambda b, n, l, *split: device_tree._route(
            b, n, l, 3, split)).lower(
                arg((rows, F), dtype), arg((rows,), i32), arg((rows,), i32),
                arg((S,), i32), arg((S,), i32), arg((S,), i32),
                arg((S, maxB), jnp.bool_)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes < 16 * rows
        hlo = compiled.as_text()
        shapes = dict(re.findall(
            r"^\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]", hlo, re.M))
        assert not [shapes[op] for op in re.findall(
            r" gather\((%[\w.\-]+),", hlo)
            if shapes[op].split(",")[0] == str(rows)]


def test_tree_program_fits_one_chip_at_32m_rows(one_chip):
    """`jit_tree_program` compiled for a v5e at 32,000,000 x 28 rows, H2O's
    4 x data sizing of a 16 GB chip (ISSUE 35): until the leaf pass stopped
    stacking its four columns into an `(N, 4)` f32 array, whose minor axis a
    TPU pads to 128 lanes (512 B a row: 15.27 GB here), the compiler
    refused this size. The program's scratch is now a few `(N,)` vectors
    (70 B a row measured; the bound leaves less room than one more padded
    `(N, 4)` or an `(N, 28)` copy of the bins would take), no buffer of the
    program has that shape, and scratch, arguments and results together
    leave the frame's 3.6 GB beside them on the chip."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.models.tree import device_tree

    rows, F, maxB, depth = 32_000_000, 28, 21, 5
    mesh = Mesh(np.array(list(one_chip.device_set)), ("rows",))

    def sharded(shape, dt, *axes):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, P(*axes)))

    grow = device_tree._grow_fn(
        depth, F, maxB, (maxB,) * F, (False,) * F, 10.0, 1e-5, False, mesh,
        rows, device_tree._pick_blk(rows, F * maxB),
        device_tree.frontier_cap(F, maxB))
    f32 = sharded((rows,), jnp.float32, "rows")
    compiled = grow.lower(sharded((rows, F), jnp.uint8, "rows", None), f32,
                          f32, f32, f32, np.zeros(0, np.float32)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 96 * rows
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 6 * 2 ** 30
    assert not re.findall(r"f32\[\d{7,},4\]", compiled.as_text())

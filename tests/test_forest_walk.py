"""The forest walk (ISSUE 27): margins and leaf ids of the device traversal
are bitwise those of a row-at-a-time walk on the host, over every kind of
forest that runs the one step (`compressed._step`); the step gathers from no
operand that carries the rows; `h2o3_forest_walk_total` says which form ran.

Tiny frames on the CPU mesh: what is asserted is equality and structure,
never a time."""

import numpy as np
import pytest

from h2o3_tpu.models.tree import compressed
from h2o3_tpu.models.tree.compressed import CompressedForest
from h2o3_tpu.obs import metrics

N = 236          # rows; no table of any case below has this many entries


def _forest(seed, F, na_bins, depth, *, split_p=1.0, cat_feats=(), T=5,
            K=1):
    """A random forest as CompressedForest holds it. Node ids are handed
    out in the order nodes are reached, so left/right are not 2m+1/2m+2;
    `split_p` < 1 leaves branches short (uneven trees); a split on a
    feature in `cat_feats` is a categorical subset over all of its bins."""
    rng = np.random.default_rng(seed)
    maxB = int(na_bins.max()) + 1
    trees, cat_rows = [], []
    for _ in range(T):
        nodes = [dict(depth=0)]
        todo = [0]
        while todo:
            m = todo.pop(rng.integers(len(todo)))
            nd = nodes[m]
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


# name -> (forest kwargs, dtype of the bin matrix, the form it must count as)
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
    "deep_uneven_d13": (dict(F=7, na_bins=np.full(7, 20), depth=13,
                             split_p=.97, T=3), np.uint8, "gather"),
    "one_feature": (dict(F=1, na_bins=np.array([20]), depth=3), np.uint8,
                    "select"),
    "f300": (dict(F=300, na_bins=np.full(300, 20), depth=5), np.uint8,
             "select"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_walk_is_bitwise_the_host_walk(name):
    kw, dtype, form = CASES[name]
    fo = _forest(sum(map(ord, name)), **kw)
    binned = _bins(1, kw["na_bins"], dtype)
    want_margin, want_leaves = _host_walk(fo, binned)
    assert fo.walk_form == form
    if name == "uneven_d9_hundreds_of_nodes":
        assert 200 < fo.feat.shape[1] <= compressed._SELECT_MAX_NODES
    if name == "deep_uneven_d13":        # the other side of _at_node's rule
        assert fo.feat.shape[1] > compressed._SELECT_MAX_NODES
    if name == "cat_and_numeric_trees":      # both sides of the cond
        has_cat = (fo.cat_split >= 0).any(axis=1)
        assert has_cat.any() and not has_cat.all()
    assert _same_bits(fo.predict_binned(binned), want_margin)
    assert _same_bits(fo.leaf_index(binned), want_leaves)


@pytest.mark.parametrize("name", ["higgs_d5_u8", "cat_and_numeric_trees",
                                  "uneven_d9_hundreds_of_nodes"])
def test_tables_by_select_walk_like_the_host(name, monkeypatch):
    """The TPU's form of the node-table lookup (XLA:CPU lowers the gather
    form: _at_node), run here on the CPU in the CPU form's place, in fresh
    programs: the same bits."""
    import jax

    monkeypatch.setattr(compressed, "_tables_by_gather",
                        compressed._tables_by_select)
    kw, dtype, _ = CASES[name]
    fo = _forest(sum(map(ord, name)), **kw)
    binned = _bins(1, kw["na_bins"], dtype)
    want_margin, want_leaves = _host_walk(fo, binned)
    a = fo.arrays()
    jaxpr = jax.make_jaxpr(lambda b, *a: compressed._forest_margins(
        b, *a, fo.max_depth, 1))(binned, *a).jaxpr
    gathered = [e.invars[0].aval for e in _eqns(jaxpr)
                if e.primitive.name == "gather"]
    assert gathered and all(            # leaf values and cat_table only
        v.ndim == 2 or v.dtype == np.float32 for v in gathered), gathered
    got = jax.jit(lambda b, *a: compressed._forest_margins(
        b, *a, fo.max_depth, 1))(binned, *a)
    assert _same_bits(got, want_margin)
    got = jax.jit(lambda b, *a: compressed._forest_leaves(
        b, a[0], a[1], a[2], a[3], a[4], a[6], a[7], a[9],
        fo.max_depth))(binned, *a)
    assert _same_bits(got, want_leaves)


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
    feat, thresh, na_left, left, right, _, cat_split, cat_table, _, nb = \
        arrays
    got = leaf(Xd, *tables, feat, thresh, na_left, left, right, cat_split,
               cat_table, nb)
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
    row count (a per-row gather has no hardware on a TPU). Gathers from the
    (M,) leaf values and, in the other branch of the cond, from cat_table
    stay."""
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
    assert gathers                       # cat_table's, in the cat branch
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
    kw, dtype, _ = CASES["higgs_d5_u8"]
    numeric, bn = _forest(1, **kw), _bins(3, kw["na_bins"], dtype, 16)
    kw, dtype, _ = CASES["cat_bins_above_255"]
    cat, bc = _forest(2, **kw), _bins(3, kw["na_bins"], dtype, 16)
    kw, dtype, _ = CASES["deep_uneven_d13"]
    deep, bd = _forest(3, **kw), _bins(3, kw["na_bins"], dtype, 16)
    before = _walks()
    numeric.predict_binned(bn)
    numeric.leaf_index(bn)
    cat.predict_binned(bc)
    deep.leaf_index(bd)
    assert _delta(before) == {"select": 2, "select+cat": 1, "gather": 1}


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


def test_training_metrics_walk_compiles_for_the_chip_without_a_row_gather(
        one_chip):
    """`gbm_train`'s training-metrics traversal at its real shapes (16M x 28
    u8 bins, 5 trees of 63 nodes), compiled for a v5e: the select over the
    feature axis fuses into reduces over the bin matrix as it lies (no
    (N, 28) intermediate: the program's scratch stays under one more copy
    of the matrix, 0.53 GB is the loop carries), and no gather reads it."""
    import re

    import jax
    import jax.numpy as jnp

    rows, F, T, M = 16_000_000, 28, 5, 63

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, tm = jnp.int32, (T, M)
    lowered = compressed._traverse_fn(5, 2).lower(
        arg((rows, F), jnp.uint8), arg(tm, i32), arg(tm, i32),
        arg(tm, jnp.bool_), arg(tm, i32), arg(tm, i32),
        arg(tm, jnp.float32), arg(tm, i32), arg((1, 21), jnp.bool_),
        arg((T,), i32), arg((F,), i32))
    compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * rows * F
    # lowered for a TPU the node tables are selected, not gathered: what is
    # left to gather is the leaf value a tree and cat_table in its branch
    assert len(re.findall(r"stablehlo\.gather\"?\(", lowered.as_text())) == 2
    text = compiled.as_text()
    assert "reduce(" in text
    shapes = dict(re.findall(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]", text, re.M))
    gathered = re.findall(r" gather\((%[\w.\-]+),", text)
    assert gathered                      # cat_table's, in the cat branch
    assert not [shapes[op] for op in gathered
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

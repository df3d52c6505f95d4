"""Routing in the tree program (ISSUE 29): one level of `device_tree._route`
is bit for bit the plain `left_table[node, bin]` lookup, in both forms of
its table reads and on both sides of `_at_node`'s rule; a whole tree grown
with it is the tree the per-row-gather form grows; `apply_packed` lands every
training row on its own leaf; `h2o3_tree_route_levels_total` and the `trees`
span name the forms from static widths alone.

Tiny frames on the 8-device CPU mesh: what is asserted is equality and
structure, never a time. The program lowered for a TPU at the cells' real
shapes is held in tests/test_forest_walk.py, beside the one fixture that
describes a chip."""

import types

import numpy as np
import pytest

from h2o3_tpu.models.tree import compressed, device_tree
from h2o3_tpu.obs import metrics, tracing
from tests.test_forest_walk import _eqns

N = 488          # rows of a one-level case; no table below has this many
AIRLINE_NBINS = (13, 32, 8, 23, 301, 301, 101, 101)
W301 = device_tree.route_words(301)

# name -> (nbins incl. the NA bin, bin dtype, slots S of the level)
LEVELS = {
    "u8_F28_maxB21_S32": ((21,) * 28, np.uint8, 32),
    "int16_maxB301_S8": (AIRLINE_NBINS, np.int16, 8),
    "int16_maxB301_below_the_rule": (
        AIRLINE_NBINS, np.int16, compressed._SELECT_MAX_NODES // W301),
    "int16_maxB301_above_the_rule": (
        AIRLINE_NBINS, np.int16, compressed._SELECT_MAX_NODES // W301 + 1),
}


def _level(name):
    """One level's inputs: random tables with terminal slots, rows of which
    a tenth are dead (already on a leaf) and a fifth sit in an NA bin, whose
    side the table decides like any other bin's."""
    nbins, dtype, S = LEVELS[name]
    rng = np.random.default_rng(sum(map(ord, name)))
    nb = np.asarray(nbins)
    F, maxB = len(nb), int(nb.max())
    binned = rng.integers(0, nb[None, :], (N, F))
    na = rng.random((N, F)) < 0.2
    binned = np.where(na, nb[None, :] - 1, binned).astype(dtype)
    row_node = rng.integers(0, S, N).astype(np.int32)
    row_leaf = np.where(rng.random(N) < 0.1, 5, -1).astype(np.int32)
    split_feat = np.where(np.arange(S) % 5 == 1, -1,      # terminal slots
                          rng.integers(0, F, S)).astype(np.int32)
    keep = split_feat >= 0
    excl = np.cumsum(keep) - keep
    left_slot = np.where(keep, 2 * excl, -1).astype(np.int32)
    right_slot = np.where(keep, 2 * excl + 1, -1).astype(np.int32)
    left_table = rng.random((S, maxB)) < 0.5
    return binned, row_node, row_leaf, (split_feat, left_slot, right_slot,
                                        left_table)


def _plain_route(binned, row_node, row_leaf, gid0, split):
    """The level as numpy indexing says it."""
    live = row_leaf < 0
    if split is None:
        return row_node, np.where(live, gid0 + row_node, row_leaf)
    split_feat, left_slot, right_slot, left_table = split
    f = split_feat[row_node]
    terminal = f < 0
    b = binned[np.arange(len(row_node)), np.maximum(f, 0)].astype(np.int64)
    go_left = left_table[row_node,
                         np.minimum(b, left_table.shape[1] - 1)]
    child = np.where(go_left, left_slot[row_node], right_slot[row_node])
    return (np.where(live & ~terminal, child, 0),
            np.where(live & terminal, gid0 + row_node, row_leaf))


def _route_by_row_gather(binned, row_node, row_leaf, gid0, split):
    """The form the tree program had before ISSUE 29 (a per-row gather of
    the bin, a 2-D gather of left_table), kept here as the reference a
    whole tree is grown with."""
    import jax.numpy as jnp

    live = row_leaf < 0
    if split is None:
        return row_node, jnp.where(live, gid0 + row_node, row_leaf)
    split_feat, left_slot, right_slot, left_table = split
    terminal = split_feat[row_node] < 0
    row_leaf = jnp.where(live & terminal, gid0 + row_node, row_leaf)
    f_sel = jnp.maximum(split_feat[row_node], 0)
    b = jnp.take_along_axis(binned, f_sel[:, None], axis=1)[:, 0]
    gl = left_table[row_node, jnp.minimum(b, left_table.shape[1] - 1)]
    return (jnp.where(live & ~terminal,
                      jnp.where(gl, left_slot[row_node],
                                right_slot[row_node]), 0), row_leaf)


@pytest.mark.parametrize("form", ["as_lowered_here", "tables_by_select"])
@pytest.mark.parametrize("name", sorted(LEVELS))
def test_one_level_is_the_plain_table_lookup(name, form, monkeypatch):
    """`tables_by_select` is the TPU's form of every table read (XLA:CPU
    lowers the gather form: _at_node), run here in its place, above the
    rule too; `as_lowered_here` is what tier-1 otherwise runs."""
    import jax

    if form == "tables_by_select":
        monkeypatch.setattr(compressed, "_tables_by_gather",
                            compressed._tables_by_select)
    binned, row_node, row_leaf, split = _level(name)
    S = len(split[0])
    assert (compressed.table_form(S * device_tree.route_words(
        split[3].shape[1])) == "gather") == name.endswith("above_the_rule")
    want = _plain_route(binned, row_node, row_leaf, 77, split)
    assert (want[0] > 0).any() and (want[1] >= 77).any()
    fn = jax.jit(lambda *a: device_tree._route(*a[:3], 77, a[3]))
    got = fn(binned, row_node, row_leaf, split)
    for g, w in zip(got, want):
        assert np.asarray(g).dtype == np.int32
        assert np.array_equal(np.asarray(g), w)
    gathered = [e.invars[0].aval for e in _eqns(jax.make_jaxpr(
        lambda *a: device_tree._route(*a[:3], 77, a[3]))(
            binned, row_node, row_leaf, split).jaxpr)
        if e.primitive.name == "gather"]
    assert all(v.ndim == 1 and v.shape[0] != N for v in gathered), gathered
    if form == "tables_by_select":
        assert not gathered


def test_the_last_level_reads_no_table():
    """At max_depth every slot is terminal by construction: a live row gets
    its leaf id, and nothing of the bin matrix or a table is read."""
    import jax

    binned, row_node, row_leaf, _ = _level("u8_F28_maxB21_S32")
    want = _plain_route(binned, row_node, row_leaf, 31, None)
    jaxpr = jax.make_jaxpr(lambda b, n, l: device_tree._route(
        b, n, l, 31, None))(binned, row_node, row_leaf)
    got = jax.jit(lambda b, n, l: device_tree._route(b, n, l, 31, None))(
        binned, row_node, row_leaf)
    assert np.array_equal(np.asarray(got[0]), want[0])
    assert np.array_equal(np.asarray(got[1]), want[1])
    assert (want[1] >= 0).all()
    names = {e.primitive.name for e in _eqns(jaxpr.jaxpr)}
    assert not names & {"gather", "reduce_sum", "reduce_or", "dot_general"}


# whole trees: name -> (nbins, is_cat, bin dtype, max_depth)
TREES = {
    "numeric_u8_d5": ((21,) * 6, (False,) * 6, np.uint8, 5),
    "enum_int16_maxB301_d6": (AIRLINE_NBINS, (True,) * 6 + (False,) * 2,
                              np.int16, 6),
}


def _tree_inputs(name, cl):
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    nbins, is_cat, dtype, depth = TREES[name]
    mesh = device_tree._mesh()
    assert device_tree._mesh_size(mesh) == 8     # shard_map over 8 devices
    rng = np.random.default_rng(len(name))
    nb = np.asarray(nbins)
    n = 8 * 250
    binned = rng.integers(0, nb[None, :] - 1, (n, len(nb)))
    binned = np.where(rng.random(binned.shape) < 0.05, nb[None, :] - 1,
                      binned).astype(dtype)                 # 5% missing
    x = binned / nb[None, :]
    y = ((x[:, 0] - x[:, 1] * x[:, 4] + (binned[:, 4] % 3 == 0)
          + 0.2 * rng.standard_normal(n)) > 0.6).astype(np.float32)
    spec = types.SimpleNamespace(nbins=nb, is_cat=np.asarray(is_cat),
                                 F=len(nb))

    def put(a, *axes):
        return jax.device_put(a, NamedSharding(mesh, P(*axes)))

    return (put(binned, "rows", None), put(np.ones(n, np.float32), "rows"),
            put(y, "rows")), spec, depth


def _grow(args, spec, depth):
    device_tree._grow_fn.cache_clear()
    try:
        return [np.asarray(a) for a in device_tree.grow_tree_device(
            *args, spec, max_depth=depth, min_rows=2.0,
            min_split_improvement=1e-5)]
    finally:
        device_tree._grow_fn.cache_clear()


@pytest.mark.parametrize("name", sorted(TREES))
def test_a_whole_tree_is_the_tree_the_row_gather_form_grows(name, cl,
                                                            monkeypatch):
    """`packed`, `leaf4` and `row_leaf` of one tree, under shard_map over
    the 8-device mesh, against the same program with the old route step in
    `_route`'s place; then `apply_packed` on the training block reads every
    row's own leaf, in both forms."""
    import jax.numpy as jnp

    args, spec, depth = _tree_inputs(name, cl)
    maxB = int(spec.nbins.max())
    packed, leaf4, row_leaf = _grow(args, spec, depth)
    splits = packed[:, :, 0][packed[:, :, 3] > 0]
    assert len(splits) > 2 ** (depth - 2)           # a tree, not a stump
    if name.startswith("enum"):
        assert np.asarray(spec.is_cat)[splits.astype(int)].any()
    values = np.random.default_rng(1).standard_normal(
        leaf4.shape[0]).astype(np.float32)

    def applied():
        device_tree._apply_fn.cache_clear()
        try:
            return np.asarray(device_tree.apply_packed(
                args[0], jnp.asarray(packed), jnp.asarray(values), depth,
                maxB))
        finally:
            device_tree._apply_fn.cache_clear()

    assert np.array_equal(applied(), values[row_leaf])
    monkeypatch.setattr(device_tree, "_route", _route_by_row_gather)
    for got, want in zip(_grow(args, spec, depth),
                         (packed, leaf4, row_leaf)):
        assert got.dtype == want.dtype and np.array_equal(got, want)
    assert np.array_equal(applied(), values[row_leaf])


def _by_label(counter, label):
    return {s["labels"].get(label): s["value"] for s in
            metrics.REGISTRY.get(counter).snapshot()["samples"]
            if s["labels"]}


def _routed():
    return _by_label("h2o3_tree_route_levels_total", "form")


def _moved(before):
    now = _routed()
    return {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}


def test_route_forms_follow_the_one_rule():
    """depth 5 at maxB 21: one word a slot, at most 16 entries, all select;
    maxB 301 at depth 10: ten words a slot, level 8 holds 2,560 (select),
    level 9 holds 5,120 (gather); depth 20 at one word a slot stays under
    the rule at the frontier cap's 4,096 slots, at three words a slot it
    gathers from 2,048 slots on."""
    assert device_tree.route_forms(5, 28, 21) == ("select",) * 5
    assert device_tree.route_forms(10, 8, 301) == ("select",) * 9 + (
        "gather",)
    assert device_tree.frontier_cap(28, 21) == compressed._SELECT_MAX_NODES
    assert device_tree.route_forms(20, 28, 21) == ("select",) * 20
    assert device_tree.route_forms(20, 8, 65) == ("select",) * 11 + (
        "gather",) * 9


def test_counter_and_trees_span_name_the_forms_on_the_host():
    """Counted from static widths when a tree is dispatched: nothing
    crosses to or from a device, nothing compiles."""
    import jax

    before = _routed()
    compiles = metrics.REGISTRY.get("h2o3_backend_compiles_total").snapshot()
    with tracing.root_span("ingress", path="/3/ModelBuilders/gbm") as root:
        with tracing.span("trees"):
            with jax.transfer_guard("disallow"):
                for _ in range(2):                   # two trees of a job
                    device_tree._count_route(
                        device_tree.route_forms(10, 8, 301))
                device_tree._count_route(device_tree.route_forms(5, 28, 21))
    assert _moved(before) == {"select": 2 * 9 + 5, "gather": 2}
    assert metrics.REGISTRY.get(
        "h2o3_backend_compiles_total").snapshot() == compiles
    trees, = [s for s in tracing.get_trace(root.span["trace_id"],
                                           include_remote=False)
              if s["name"] == "trees"]
    assert trees["attrs"]["route_levels"] == 25
    assert trees["attrs"]["route_gather_levels"] == 2


def _hist_levels():
    return _by_label("h2o3_tree_hist_levels_total", "lowering")


@pytest.mark.parametrize("depth,F,maxB,matmul,scatter", [
    (5, 28, 21, 5, 0),       # higgs_gbm_d5: widest level 16 slots
    (10, 8, 301, 10, 0),     # airline_gbm_d10: widest level 512 slots
    (20, 28, 21, 11, 9),     # DRF's default depth: level 11 is 2,048 wide
])
def test_hist_levels_are_counted_by_lowering_on_the_host(depth, F, maxB,
                                                         matmul, scatter):
    """h2o3_tree_hist_levels_total{lowering} and the `trees` span's
    hist_matmul_levels / hist_scatter_levels split a tree's levels where
    hist_lowering does, at MATMUL_S_LIMIT slots, from static widths:
    nothing crosses to or from a device, nothing compiles."""
    import jax

    forms = device_tree.hist_forms(depth, F, maxB)
    assert forms == ("matmul",) * matmul + ("scatter",) * scatter
    widths = device_tree.level_widths(depth,
                                      device_tree.frontier_cap(F, maxB))
    assert [S <= device_tree.MATMUL_S_LIMIT for S in widths[:depth]] == \
        [f == "matmul" for f in forms]
    before = _hist_levels()
    compiles = metrics.REGISTRY.get("h2o3_backend_compiles_total").snapshot()
    with tracing.root_span("ingress", path="/3/ModelBuilders/gbm") as root:
        with tracing.span("trees"):
            with jax.transfer_guard("disallow"):
                for _ in range(2):                   # two trees of a job
                    device_tree._count_hist(forms)
    now = _hist_levels()
    assert {k: now[k] - before.get(k, 0) for k in now} == \
        {"matmul": 2 * matmul, "scatter": 2 * scatter}
    assert metrics.REGISTRY.get(
        "h2o3_backend_compiles_total").snapshot() == compiles
    trees, = [s for s in tracing.get_trace(root.span["trace_id"],
                                           include_remote=False)
              if s["name"] == "trees"]
    assert trees["attrs"]["hist_matmul_levels"] == 2 * matmul
    assert trees["attrs"]["hist_scatter_levels"] == 2 * scatter


@pytest.mark.parametrize("depth,F,maxB,form", [
    (5, 28, 21, "matmul"),       # higgs_gbm_d5: 64 slots
    (10, 8, 301, "matmul_split"),    # airline_gbm_d10: 2,048 slots
    (20, 28, 21, "matmul_split"),    # DRF's default depth: 40,960 slots
])
def test_leaf_passes_are_counted_by_lowering_on_the_host(depth, F, maxB,
                                                         form):
    """h2o3_tree_leaf_sums_total{lowering} moves by one a tree and the
    `trees` span says which form its trees' leaf pass took, from
    leaf_split's rule on the tree's static slots: nothing crosses to or
    from a device, nothing compiles."""
    import jax

    assert device_tree.leaf_forms(depth, F, maxB) == form
    counted = lambda: _by_label("h2o3_tree_leaf_sums_total", "lowering")
    before = counted()
    compiles = metrics.REGISTRY.get("h2o3_backend_compiles_total").snapshot()
    with tracing.root_span("ingress", path="/3/ModelBuilders/gbm") as root:
        with tracing.span("trees"):
            with jax.transfer_guard("disallow"):
                for _ in range(2):                   # two trees of a job
                    device_tree._count_leaf(form)
    now = counted()
    assert {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)} == {form: 2}
    assert metrics.REGISTRY.get(
        "h2o3_backend_compiles_total").snapshot() == compiles
    trees, = [s for s in tracing.get_trace(root.span["trace_id"],
                                           include_remote=False)
              if s["name"] == "trees"]
    assert trees["attrs"]["leaf_lowering"] == form


def test_a_fit_counts_its_trees_levels_and_adds_no_compile_or_change(cl):
    """A depth-3 GBM of 3 trees: 9 routing levels by select on the `trees`
    span and the counter; the same fit again compiles nothing, and the
    forest is the one a fit that counts nothing grows."""
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.models.tree.gbm import GBM

    rng = np.random.default_rng(4)
    fr = Frame()
    x1, x2 = rng.standard_normal(1200), rng.standard_normal(1200)
    fr.add("x1", Column.from_numpy(x1))
    fr.add("x2", Column.from_numpy(x2))
    fr.add("y", Column.from_numpy(np.where(
        rng.random(1200) < 1 / (1 + np.exp(x2 - x1)), "Y", "N"),
        ctype="enum"))

    def fit():
        return GBM(ntrees=3, max_depth=3, seed=7).train(
            y="y", training_frame=fr)

    def compiles():
        return sum(s["value"] for s in metrics.REGISTRY.get(
            "h2o3_backend_compiles_total").snapshot()["samples"])

    first = fit()
    before, hist_before, compiled = _routed(), _hist_levels(), compiles()
    with tracing.root_span("ingress", path="/3/ModelBuilders/gbm") as root:
        second = fit()
    assert _moved(before) == {"select": 9}
    assert {k: v - hist_before.get(k, 0)
            for k, v in _hist_levels().items()} == {"matmul": 9, "scatter": 0}
    assert compiles() == compiled
    trees, = [s for s in tracing.get_trace(root.span["trace_id"],
                                           include_remote=False)
              if s["name"] == "trees"]
    assert trees["attrs"]["route_levels"] == 9
    assert trees["attrs"]["route_gather_levels"] == 0
    assert trees["attrs"]["hist_matmul_levels"] == 9
    assert trees["attrs"]["hist_scatter_levels"] == 0
    for a, b in zip(first.forest.arrays(), second.forest.arrays()):
        assert np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)

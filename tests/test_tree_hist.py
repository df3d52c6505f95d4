"""The level histogram of the one-tree program (device_tree.py): each
lowering alone against a float64 loop, the rule that picks one from a
level's width, and a forest grown under either.

A third lowering is a third function of the same signature: add it to
LOWERINGS with the rounding its operands allow and the same cases judge it.
"""

import json
from pathlib import Path

import numpy as np
import pytest

from h2o3_tpu.core.frame import Column, Frame
from h2o3_tpu.models.tree import device_tree
from h2o3_tpu.ops import segsum

ROOT = Path(__file__).resolve().parent.parent

# lowering -> the share of a term's magnitude its sum may be off by.
# hist_matmul rounds each (w, w·y, w·y²) term to bf16 (8 significand bits,
# to nearest: 2^-8 of the term; the bin one-hot is exact) and accumulates in
# f32;
# hist_scatter makes and adds the terms in f32 (2^-24 a product and an add,
# over at most a thousand rows a cell: 2^-13 is loose, and the matmul gets
# it on top for the same f32 steps). _truth returns the sums of the terms'
# magnitudes beside the sums.
LOWERINGS = {"matmul": (device_tree.hist_matmul, 2.0 ** -8 + 2.0 ** -13),
             "scatter": (device_tree.hist_scatter, 2.0 ** -13)}


# the two benchmark configurations' lane layouts: higgs_gbm_d5 is 28
# features of 21 bins; airline_gbm_d10 is 6 enum + 2 numeric columns, every
# count one more than its levels or nbins for the missing
AIRLINE_NBINS = (13, 32, 8, 23, 301, 301, 101, 101)


def _case(seed, n, F, maxB, S, *, dead_frac=0.15, zero_w_frac=0.1,
          ragged_bins=False):
    """Synthetic rows mixing the grower's edge shapes: a reserved NA bin
    (the last bin of every feature, overweighted), dead rows (node = -1:
    routed to a leaf), zero-weight live rows (sampled out), and optionally
    ragged per-feature bin counts (categorical cardinalities): drawn, or
    the tuple given."""
    rng = np.random.default_rng(seed)
    if ragged_bins:
        if ragged_bins is True:
            nbins = rng.integers(2, maxB + 1, F).astype(np.int64)
        else:
            nbins = np.asarray(ragged_bins, np.int64)
            assert len(nbins) == F and nbins.max() == maxB
    else:
        nbins = np.full(F, maxB, np.int64)
    binned = np.stack([rng.integers(0, nbins[f], n) for f in range(F)],
                      axis=1).astype(np.int32)
    na_rows = rng.random(n) < 0.2
    binned[na_rows] = (nbins - 1)[None, :]
    node = rng.integers(0, S, n).astype(np.int32)
    node[rng.random(n) < dead_frac] = -1
    w = rng.random(n).astype(np.float32) + 0.25
    w[rng.random(n) < zero_w_frac] = 0.0
    y = rng.standard_normal(n).astype(np.float32)
    return binned, node, w, y, tuple(int(b) for b in nbins)


def _truth(binned, node, w, y, S, maxB):
    """(S, F, maxB, 3) float64 sums of (w, w·y, w·y²) over live rows, one
    row and feature at a time, and the sums of the terms' magnitudes."""
    F = binned.shape[1]
    out = np.zeros((S, F, maxB, 3), np.float64)
    mag = np.zeros_like(out)
    for r in range(binned.shape[0]):
        if node[r] < 0 or w[r] == 0.0:
            continue
        wr, yr = float(w[r]), float(y[r])
        t = np.array([wr, wr * yr, wr * yr * yr])
        for f in range(F):
            out[node[r], f, binned[r, f]] += t
            mag[node[r], f, binned[r, f]] += np.abs(t)
    return out, mag


def _run(cl, lowering, binned, node, w, y, S, nbins, blk):
    """One lowering alone under shard_map over the cluster's mesh, the
    rows laid out as tree_program lays them: every shard a multiple of blk
    rows, the padding dead."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.compat import shard_map

    fn, _ = LOWERINGS[lowering]
    n_dev = cl.mesh.devices.size
    blocks = -(-len(node) // (n_dev * blk))       # row blocks a shard
    pad = n_dev * blocks * blk - len(node)
    live = np.pad(node >= 0, (0, pad))
    args = (np.pad(binned, ((0, pad), (0, 0))),
            np.pad(np.maximum(node, 0), (0, pad)), live,
            np.pad(w, (0, pad)), np.pad(y, (0, pad)))

    def local(binned, row_node, live, w, y):
        return fn(binned, row_node, live, w, y, S, nbins=nbins,
                  maxB=max(nbins), blk=blk)

    run = jax.jit(shard_map(
        local, mesh=cl.mesh,
        in_specs=(P("rows", None), P("rows"), P("rows"), P("rows"),
                  P("rows")),
        out_specs=P()))
    return np.asarray(run(*(jnp.asarray(a) for a in args)), np.float64)


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
@pytest.mark.parametrize("seed,n,F,maxB,S,blk,ragged", [
    (0, 1000, 5, 8, 12, 64, False),    # ragged rows: 125 a shard, blocks of 64
    (1, 512, 3, 6, 7, 32, True),       # ragged bins, two blocks a shard
    (2, 768, 8, 16, 16, 32, False),    # three blocks a shard, aligned
    (3, 300, 2, 4, 3, 16, True),       # rows that do not divide by the mesh
    (4, 256, 1, 32, 5, 32, False),     # single feature, wide bins
    (5, 640, 28, 21, 16, 32, False),   # higgs_gbm_d5's lanes, its widest level
    (6, 512, 8, 301, 1, 32, AIRLINE_NBINS),    # airline_gbm_d10's lanes, bins
    (7, 512, 8, 301, 16, 32, AIRLINE_NBINS),   # past 255 in the data, from the
    (8, 512, 8, 301, 512, 32, AIRLINE_NBINS),  # root to its widest level
])
def test_hist_against_f64_truth(cl, lowering, seed, n, F, maxB, S, blk,
                                ragged):
    binned, node, w, y, nbins = _case(seed, n, F, maxB, S,
                                      ragged_bins=ragged)
    assert maxB <= 256 or (binned >= 256).any()
    got = _run(cl, lowering, binned, node, w, y, S, nbins, blk)
    want, mag = _truth(binned, node, w, y, S, max(nbins))
    assert got.shape == want.shape
    slack = LOWERINGS[lowering][1] * mag + 1e-6
    worst = np.abs(got - want) - slack
    assert np.all(worst <= 0), \
        f"{lowering}: off by {worst.max():.3g} beyond its rounding at " \
        f"{np.argwhere(worst > 0)[:5]}"
    # a lane no row can fall in (bin >= nbins[f]) holds an exact zero
    for f, nb in enumerate(nbins):
        assert np.all(got[:, f, nb:] == 0)


def _matmul_on_concatenated_one_hots(binned, row_node, live, w, y, S, *,
                                     nbins, maxB, blk):
    """hist_matmul as it built its operands until PR 31, kept here as the
    plain reference: a jax.nn.one_hot a feature laid side by side into
    (blk, lanes), the (w, w·y, w·y²) triples crossed with the slot one-hot
    and interleaved into (blk, 3S), one dot a block. Slow on a TPU (every
    piece lands at a lane offset inside a tile); exact anywhere."""
    import jax
    import jax.numpy as jnp

    F = len(nbins)
    acc = jnp.zeros((sum(nbins), S * 3), jnp.float32)
    for i in range(binned.shape[0] // blk):
        rows = slice(i * blk, (i + 1) * blk)
        wb = jnp.where(live[rows], w[rows], 0.0)
        yb = y[rows]
        Ob = jnp.concatenate(
            [jax.nn.one_hot(binned[rows, f], nbins[f], dtype=jnp.bfloat16)
             for f in range(F)], axis=1)
        node_oh = jax.nn.one_hot(row_node[rows], S, dtype=jnp.float32)
        vals = jnp.stack([wb, wb * yb, wb * yb * yb], axis=-1)
        V = (node_oh[:, :, None] * vals[:, None, :]).reshape(blk, S * 3)
        acc = acc + jnp.dot(Ob.T, V.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
    acc = jax.lax.psum(acc, "rows")
    acc = jnp.concatenate(
        [jnp.pad(acc[o:o + nb], ((0, maxB - nb), (0, 0)))
         for o, nb in zip(np.cumsum((0,) + nbins[:-1]), nbins)])
    return acc.reshape(F, maxB, S, 3).transpose(2, 0, 1, 3)


@pytest.mark.parametrize("seed,n,F,maxB,S,blk,ragged,dtype", [
    (20, 768, 28, 21, 16, 32, False, "uint8"),     # higgs_gbm_d5's lanes
    (20, 768, 28, 21, 1, 64, False, "int32"),
    (21, 512, 6, 40, 5, 32, True, "uint8"),        # ragged, odd widths
    (21, 512, 6, 40, 5, 32, True, "int16"),
    (22, 512, 8, 301, 16, 32, AIRLINE_NBINS, "int16"),  # airline_gbm_d10's
    (22, 512, 8, 301, 3, 32, AIRLINE_NBINS, "int32"),
])
def test_matmul_is_bit_for_bit_the_concatenated_one_hots(
        cl, monkeypatch, seed, n, F, maxB, S, blk, ragged, dtype):
    """However hist_matmul lays zeros and ones into lanes, they are the
    zeros and ones of the one-hot a feature: on the same rows it returns
    the very array a dot on the concatenated one-hots returns, in every
    dtype bin_columns packs bins in."""
    binned, node, w, y, nbins = _case(seed, n, F, maxB, S,
                                      ragged_bins=ragged)
    binned = binned.astype(dtype)
    got = _run(cl, "matmul", binned, node, w, y, S, nbins, blk)
    monkeypatch.setitem(LOWERINGS, "matmul",
                        (_matmul_on_concatenated_one_hots, 0.0))
    want = _run(cl, "matmul", binned, node, w, y, S, nbins, blk)
    assert got.shape == want.shape and got.any()
    assert np.array_equal(got, want)


@pytest.mark.parametrize("lowering", sorted(LOWERINGS))
def test_dead_and_zero_weight_rows_drop(cl, lowering):
    n, F, maxB, S, blk = 256, 3, 8, 4, 16
    binned, node, w, y, nbins = _case(12, n, F, maxB, S)
    dead = (node < 0) | (w == 0.0)
    out = _run(cl, lowering, binned, node, w, y, S, nbins, blk)
    # every live row lands once a feature, with its weight: the total is
    # the live rows' alone
    live_w = float(w[~dead].astype(np.float64).sum())
    assert out[..., 0].sum() == pytest.approx(
        F * live_w, rel=LOWERINGS[lowering][1])
    # all rows dead -> an all-zero histogram
    out0 = _run(cl, lowering, binned, np.full(n, -1, np.int32), w, y, S,
                nbins, blk)
    assert np.all(out0 == 0)


# ---------------------------------------------------------------------------
# the leaf pass: per-leaf sums of (w, w·y, num, den)
# ---------------------------------------------------------------------------

def _leaf_scatter_f32(row_leaf, w, y, num, den, tot_slots, blk):
    """The leaf pass as it was until PR 35, kept here as what the forms
    are held against: an (n, 4) f32 array scatter-added row by row into
    (tot_slots + 1, 4). blk is not used."""
    import jax
    import jax.numpy as jnp

    idx = jnp.minimum(jnp.where(row_leaf >= 0, row_leaf, tot_slots),
                      tot_slots)
    acc = jnp.zeros((tot_slots + 1, 4), jnp.float32).at[idx].add(
        jnp.stack([w, w * y, num, den], axis=-1))
    return jax.lax.psum(acc, "rows")[:tot_slots]


def _leaf_case(seed, n, tot_slots):
    """Rows as the tree program hands them to the leaf pass: a leaf slot a
    row, a third of them slots of upper levels (rows that terminalised
    early), some at tot_slots (pad rows) and some negative (a row no level
    gave a leaf: none in a grown tree, dropped all the same); weights with
    zeros among them; num and den spanning 1e-6 to 1e4, num of either
    sign."""
    rng = np.random.default_rng(seed)
    row_leaf = rng.integers(tot_slots // 2, tot_slots, n)
    early = rng.random(n) < 1 / 3
    row_leaf[early] = rng.integers(0, max(tot_slots // 2, 1), early.sum())
    row_leaf[rng.random(n) < 0.05] = tot_slots
    row_leaf[rng.random(n) < 0.02] = -1
    w = (rng.random(n) + 0.25).astype(np.float32)
    w[rng.random(n) < 0.1] = 0.0
    y = rng.standard_normal(n).astype(np.float32)
    num = (10.0 ** rng.uniform(-6, 4, n) * rng.choice([-1.0, 1.0], n)) \
        .astype(np.float32)
    den = (10.0 ** rng.uniform(-6, 4, n)).astype(np.float32)
    return row_leaf.astype(np.int32), w, y, num, den


def _leaf_truth(row_leaf, w, y, num, den, tot_slots):
    """(tot_slots, 4) float64 sums of the same f32 terms (w·y rounded to
    f32 as every form rounds it), and the sums of their magnitudes."""
    ok = (row_leaf >= 0) & (row_leaf < tot_slots)
    cols = (w, w * y, num, den)
    sums = [np.bincount(row_leaf[ok], weights=f(c[ok].astype(np.float64)),
                        minlength=tot_slots)
            for f in (lambda v: v, np.abs) for c in cols]
    return np.stack(sums[:4], -1), np.stack(sums[4:], -1)


def _run_leaf(cl, fn, row_leaf, w, y, num, den, tot_slots, blk):
    """One form alone under shard_map over the cluster's mesh, each shard
    padded with rows at tot_slots as tree_program pads them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.compat import shard_map

    pad = -len(row_leaf) % cl.mesh.devices.size
    args = [np.pad(row_leaf, (0, pad), constant_values=tot_slots)] + \
        [np.pad(a, (0, pad)) for a in (w, y, num, den)]
    run = jax.jit(shard_map(
        lambda *a: fn(*a, tot_slots, blk), mesh=cl.mesh,
        in_specs=(P("rows"),) * 5, out_specs=P()))
    return np.asarray(run(*(jnp.asarray(a) for a in args)), np.float64)


@pytest.mark.parametrize("seed,n,tot_slots,blk,form", [
    (30, 700, 1, 64, "matmul"),     # L = 2: a stump's leaf and the pad slot
    (31, 4000, 63, 96, "matmul"),   # L = 64: higgs_gbm_d5; 500 rows a shard
                                    # in blocks of 96: the last starts early
    (32, 4000, 127, 500, "matmul"),         # L = 128: the widest one-hot
    (33, 4000, 128, 500, "matmul_split"),   # L = 129: 2 x 128
    (34, 16000, 2047, 300, "matmul_split"),     # airline_gbm_d10: 8 x 256
    (35, 16000, 40959, 2000, "matmul_split"),   # depth 20: 40 x 1,024
    (36, 999, 63, 4096, "matmul"),  # a block longer than a shard's rows
])
def test_leaf_sums_against_f64_truth(cl, seed, n, tot_slots, blk, form):
    """The leaf pass in every layout leaf_split's rule returns, each
    column of every leaf within 2e-6 of the float64 sum of the same f32
    terms (relative to the sum of their magnitudes) and no further from it
    than the row-by-row f32 scatter-add is on the same rows (down to four
    f32 roundings, 2^-22: a leaf of a dozen rows and the all-reduce of
    eight shards' partials round that much in any order, either way)."""
    H, _lo = device_tree.leaf_split(tot_slots + 1)
    assert ("matmul" if H == 1 else "matmul_split") == form
    case = _leaf_case(seed, n, tot_slots)
    want, mag = _leaf_truth(*case, tot_slots)
    got = _run_leaf(cl, device_tree.leaf_sums, *case, tot_slots, blk)
    was = _run_leaf(cl, _leaf_scatter_f32, *case, tot_slots, blk)
    assert got.shape == want.shape == (tot_slots, 4)
    err = np.abs(got - want) / np.maximum(mag, 1e-30)
    err_was = np.abs(was - want) / np.maximum(mag, 1e-30)
    assert err.max() <= 2e-6, np.argwhere(err > 2e-6)[:5]
    for c in range(4):
        assert err[:, c].max() <= max(err_was[:, c].max(), 2.0 ** -22), c
    assert np.all(got[mag == 0] == 0)       # a leaf no row reaches


def test_tree_program_holds_no_n_by_4_array():
    """The one-tree program lowered and compiled at 65,536 rows, depth 5,
    on one device: nothing in it has the shape (n, 4) or (4, n) in f32, the
    stacked columns of the leaf pass whose minor axis of 4 a TPU pads to 128
    lanes (512 B a row, 15.27 GB at 32M rows: what capped a chip at 20M
    rows until PR 35). The regex is held to the old form beside it."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from h2o3_tpu.compat import shard_map

    n, F, maxB, depth = 65_536, 28, 21, 5
    mesh = Mesh(np.array(jax.devices()[:1]), ("rows",))
    grow = device_tree._grow_fn(depth, F, maxB, (maxB,) * F, (False,) * F,
                                10.0, 1e-5, False, mesh, n,
                                device_tree._pick_blk(n, F * maxB),
                                device_tree.frontier_cap(F, maxB))
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32)
    stacked = re.compile(rf"f32\[(?:{n},4|4,{n})\]")
    text = grow.lower(jax.ShapeDtypeStruct((n, F), jnp.uint8), f32, f32, f32,
                      f32, np.zeros(0, np.float32)).compile().as_text()
    assert "leaf_sums" in text and not stacked.findall(text)
    was = jax.jit(shard_map(
        lambda *a: _leaf_scatter_f32(*a, 63, 0), mesh=mesh,
        in_specs=(P("rows"),) * 5, out_specs=P())).lower(
            jax.ShapeDtypeStruct((n,), jnp.int32), f32, f32, f32, f32)
    assert stacked.findall(was.compile().as_text())


def _train_frame(seed=7, n=600):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n)
    g = np.array(["a", "b", "c"], object)[rng.integers(0, 3, n)]
    yv = np.where(rng.random(n) < 1 / (1 + np.exp(-(2 * x + (g == "a")))),
                  "Y", "N")
    fr = Frame()
    fr.add("x", Column.from_numpy(x))
    fr.add("g", Column.from_numpy(g, ctype="enum"))
    fr.add("y", Column.from_numpy(yv, ctype="enum"))
    return fr


def _train_predict(fr, **gbm_kw):
    from h2o3_tpu.models.tree.gbm import GBM

    kw = dict(ntrees=4, max_depth=3, seed=3)
    kw.update(gbm_kw)
    m = GBM(**kw).train(y="y", training_frame=fr)
    return (m.predict(fr).col("Y").to_numpy(),
            float(m._output.training_metrics.auc))


def test_gbm_forest_same_under_either_lowering(cl, monkeypatch):
    """The two lowerings are interchangeable: a forest grown with every
    level on the scatter-add predicts what the matmul's does, to
    accumulation-order tolerance. The scatter is forced where the rule
    reads it, and the compiled programs of either side are dropped so that
    neither serves the other."""
    fr = _train_frame()
    device_tree._grow_fn.cache_clear()
    try:
        assert device_tree.hist_lowering(8) is device_tree.hist_matmul
        p_mm, auc_mm = _train_predict(fr)
        monkeypatch.setattr(device_tree, "MATMUL_S_LIMIT", 0)
        device_tree._grow_fn.cache_clear()
        assert device_tree.hist_lowering(1) is device_tree.hist_scatter
        p_sc, auc_sc = _train_predict(fr)
    finally:
        device_tree._grow_fn.cache_clear()
    assert auc_sc == pytest.approx(auc_mm, abs=1e-6)
    np.testing.assert_allclose(p_sc, p_mm, atol=1e-6)


def test_cold_train_lands_tree_rows_warm_is_free(cl):
    """Every train-triggered compile lands under family `tree`; a warm
    re-train with identical params compiles ZERO new programs."""
    from h2o3_tpu.obs import compiles

    # unique geometry so this test always starts cold in-process:
    # depth 4 + n=731 is used nowhere else in the suite
    fr = _train_frame(seed=41, n=731)

    def tree_rows():
        return [r for r in compiles.ledger_rows()
                if r.get("family") == "tree" and r["cache"] == "compile"]

    def fresh(prior):
        # the ledger deque is bounded (maxlen=512): under saturation
        # appends drop rows off the FRONT, so a count-based slice
        # would miss new rows — detect them by object identity
        prior_ids = {id(r) for r in prior}
        return [r for r in tree_rows() if id(r) not in prior_ids]

    before = tree_rows()
    _train_predict(fr, ntrees=2, max_depth=4, seed=5)
    cold = fresh(before)
    assert cold, "a cold train must compile tree-family programs"
    programs = {r.get("program") for r in cold}
    assert any(p and p.startswith("tree_grow") for p in programs), programs

    hits_before = compiles.family_table().get("tree", {}) \
                                         .get("hits_memory", 0)
    mid = tree_rows()
    _train_predict(fr, ntrees=2, max_depth=4, seed=5)   # identical
    assert not fresh(mid), \
        "warm identical re-train must compile nothing"
    hits_after = compiles.family_table()["tree"]["hits_memory"]
    assert hits_after > hits_before, \
        "warm re-train must serve from the memory tier"


def test_tree_family_is_declared():
    from h2o3_tpu.obs import compiles

    assert "tree" in compiles.FAMILIES


@pytest.mark.parametrize("name,form,split", [
    ("higgs_gbm_d5", "matmul", (1, 64)),            # one one-hot
    ("drf_d6", "matmul", (1, 128)),                 # the widest one-hot
    ("drf_d7", "matmul_split", (2, 128)),
    ("airline_gbm_d10", "matmul_split", (8, 256)),  # 2,048 slots
    ("drf_d14", "matmul_split", (32, 512)),         # 16,384 slots
    ("drf_d20", "matmul_split", (40, 1024)),        # DRF's default depth
])
def test_leaf_split_rule_from_shape(name, form, split):
    """The leaf pass has one lowering, the blocked dot, and one rule for
    its layout, read from the tree's static slots: one one-hot up to a
    tile's 128 lanes, beyond it the slot split at the power of two at or
    above sqrt(12 L), where the two operands' lanes a row are fewest."""
    depth, F, maxB = (int(name[5:]), 28, 21) if name.startswith("drf_d") \
        else _config_shape(name)
    L = device_tree.total_slots(
        depth, device_tree.frontier_cap(F, maxB)) + 1
    H, lo = device_tree.leaf_split(L)
    assert (H, lo) == split and (H - 1) * lo < L <= H * lo
    assert (H, lo) == segsum.onehot_split(L, 4)     # ops/segsum's rule
    assert device_tree.leaf_forms(depth, F, maxB) == form
    assert device_tree.leaf_lanes(L) == 12 * H + lo
    if H > 1:       # no other power of two gives fewer lanes by a tile
        assert lo & (lo - 1) == 0 and all(
            12 * -(-L // o) + o > 12 * H + lo - 128
            for o in (128, 256, 512, 1024, 2048))


def _config_shape(name):
    """(max_depth, F, maxB) of a benchmark configuration, read from its
    file: a numeric column has nbins bins, an enum column its levels up to
    nbins_cats, and every column one more for the missing."""
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text(encoding="utf-8"))
    p = cfg["params"]
    cols = cfg.get("columns") or [{"type": "real"}] * cfg["features"]
    maxB = 1 + max(p["nbins"] if c["type"] != "enum"
                   else min(c["levels"], p.get("nbins_cats", 1024))
                   for c in cols)
    return p["max_depth"], len(cols), maxB


@pytest.mark.parametrize("name,scatter_from", [
    ("higgs_gbm_d5", None),
    ("airline_gbm_d10", None),
    ("drf_d20", 11),
])
def test_lowering_rule_from_shape(name, scatter_from):
    """Both benchmark configurations build every level's histogram with
    the matmul; only a forest deeper than 10 reaches the scatter-add, from
    the first level wider than MATMUL_S_LIMIT on."""
    # drf_d20: H2O-3's DRF defaults (max_depth=20, nbins=20) at HIGGS width
    depth, F, maxB = (20, 28, 21) if name == "drf_d20" \
        else _config_shape(name)
    widths = device_tree.level_widths(depth,
                                      device_tree.frontier_cap(F, maxB))
    split = depth if scatter_from is None else scatter_from
    assert [device_tree.hist_lowering(S) for S in widths[:depth]] == \
        [device_tree.hist_matmul] * split \
        + [device_tree.hist_scatter] * (depth - split)
    assert widths[split - 1] <= device_tree.MATMUL_S_LIMIT
    assert split == depth or widths[split] > device_tree.MATMUL_S_LIMIT

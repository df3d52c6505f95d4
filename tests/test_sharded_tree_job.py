"""A GBM job over 1, 2 and 4 row shards of one table (the airline recipe of
`airline_gbm_d10_100m`, at a size a test can hold): the forest does not
depend on how many devices the rows lie on, no array of the table's length is
made whole on one device, the sharded job's model passes the cell's reference
under the cell's limits, and
`h2o3_tree_psum_bytes_total{site}` / the spans' `shards` and `psum_bytes`
count what a shard hands the all-reduces, from static shapes.

Every case boots a cluster over the first k of the harness's eight virtual
devices for the length of one fit and puts the session's cluster back.
"""

import contextlib
import functools
import json
import os

import numpy as np
import pytest

from bench.harness import data_airline as recipe
from bench.harness import forest_enum
from bench.reference import gbm_enum_sharded
from h2o3_tpu.models.tree import device_tree
from h2o3_tpu.obs import tracing
from tests.test_airline_gbm_reference import _Sys, _counter, _delta

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 20_032        # 20,000 rounded up to tile 1, 2 and 4 shards of 8 rows
NA_VARIANT = 1.0 / 256
SEED, SEED_NA = 3_400_000_201, 3_400_000_203
# the bins of the recipe's columns as BinSpec lays them out (a level a bin
# and the missing bin; 100 value bins a numeric column and the missing bin)
NBINS = (13, 32, 8, 23, 301, 301, 101, 101)
SITES = ("hist", "leaf_sums", "stats")


@functools.lru_cache(maxsize=1)
def _cfg():
    with open(os.path.join(ROOT, "bench", "configs",
                           "airline_gbm_d10_100m.json")) as f:
        return json.load(f)


@contextlib.contextmanager
def _cluster_of(k: int):
    """The runtime's cluster is one over the first k devices until the
    block ends (tests of one worker run one after another)."""
    import jax

    from h2o3_tpu.core import runtime

    whole = runtime.cluster()
    runtime._CLUSTER = runtime.Cluster(
        runtime.OptArgs(devices=jax.devices()[:k]))
    try:
        yield runtime._CLUSTER
    finally:
        runtime._CLUSTER = whole


@functools.lru_cache(maxsize=8)
def _fit(k: int, seed: int, na_share=None) -> dict:
    """One traced job on k shards -> the forest as plain arrays, the
    reference's numbers for it, what the psum counter moved by and the
    job's spans."""
    import jax

    from h2o3_tpu.core.dkv import DKV
    from h2o3_tpu.models.tree.gbm import GBM

    cfg = _cfg()
    key = f"sharded_job_{k}_{seed}.hex"
    with _cluster_of(k) as cl:
        out = recipe.device_columns(seed, ROWS, sharding=cl.row_sharding(),
                                    na_share=na_share)
        cols, y = out[:-1], out[-1]
        forest_enum.install_training_frame(
            _Sys(cl), key, recipe.frame_columns(), cols, y,
            recipe.RESPONSE_NAME, recipe.RESPONSE_DOMAIN)
        grow, stray = device_tree.grow_tree_device, []

        def watched(*args, **kw):
            # at every tree's dispatch: a live array of the table's length
            # that does not lie over all k devices was made whole on one
            stray.extend(
                (a.shape, str(a.dtype)) for a in jax.live_arrays()
                if a.shape[:1] == (ROWS,)
                and len(a.sharding.device_set) != k)
            return grow(*args, **kw)

        device_tree.grow_tree_device = watched
        try:
            before = _counter("h2o3_tree_psum_bytes_total")
            leaf_before = _counter("h2o3_tree_leaf_sums_total")
            with tracing.root_span("ingress",
                                   path="/3/ModelBuilders/gbm") as root:
                model = GBM(seed=1, **cfg["params"]).train(
                    y=recipe.RESPONSE_NAME, training_frame=DKV.get(key))
            moved = _delta(before, _counter("h2o3_tree_psum_bytes_total"))
            leaf_moved = _delta(leaf_before,
                                _counter("h2o3_tree_leaf_sums_total"))
            assert not stray, stray
            assert len(model.spec.bin_columns(DKV.get(key))
                       .sharding.device_set) == k
            forest = forest_enum.forest_arrays(model.forest, model.spec)
            forest["logloss"] = float(model._output.training_metrics.logloss)
            numbers = gbm_enum_sharded.check_forest(
                cols, y, cfg, forest, k_follow=int(cfg["k_follow"]))
        finally:
            device_tree.grow_tree_device = grow
            DKV.remove(key)
    spans = tracing.get_trace(root.span["trace_id"], include_remote=False)
    return {"forest": forest, "numbers": numbers, "psum": moved,
            "leaf": leaf_moved,
            "attrs": {s["name"]: s["attrs"] for s in spans},
            "nbins": tuple(int(b) for b in model.spec.nbins)}


@pytest.mark.parametrize("shards,seed,na_share", [
    (1, SEED, None), (2, SEED, None), (4, SEED, None),
    (4, SEED_NA, NA_VARIANT)])
def test_a_sharded_job_grows_the_one_shard_forest(cl, shards, seed,
                                                  na_share):
    """Splits, subsets and covers are the one-shard job's exactly; leaf
    values and the log loss to a tolerance, each with its reason."""
    one = _fit(1, seed, na_share)["forest"]
    got = _fit(shards, seed, na_share)
    forest = got["forest"]
    # the structure is a sequence of argmax decisions over gains: a shard's
    # partial sums reach the all-reduce in another order than one device's
    # block sums, so the gains differ in their last bits (about 1e-6 of a
    # gain), and a split could change only where two candidates tie that
    # closely; on these seeds none does. Row counts are whole numbers under
    # 2^24, exact in f32 in any order: the covers are bitwise.
    for name in ("feat", "thr", "na_left", "left", "right", "cat_split",
                 "cover"):
        assert np.array_equal(one[name], forest[name]), name
    assert len(one["cat_rows"]) == len(forest["cat_rows"])
    for a, b in zip(one["cat_rows"], forest["cat_rows"]):
        assert np.array_equal(a, b)
    assert forest["init_f"] == one["init_f"]      # one f32 sum of 0/1 values
    assert [e.tolist() for e in forest["edges"]] == \
        [e.tolist() for e in one["edges"]]        # a sort: no arithmetic
    if shards == 1:     # the same sums in the same order: bit for bit
        assert np.array_equal(one["leaf"], forest["leaf"])
        assert forest["logloss"] == one["logloss"]
    else:
        # a leaf is a ratio of two f32 sums over its rows (first-tree leaves
        # to 2e-5 here; the second tree's gradients carry the first's), so
        # 1e-4 of the leaf, floored at 1e-3 in absolute terms for the
        # leaves near 0, is five times the widest difference seen on
        # either seed and a thousandth of what a wrong row would move
        scale = np.maximum(np.abs(one["leaf"]), 1e-3)
        assert np.max(np.abs(one["leaf"] - forest["leaf"]) / scale) < 1e-4
        # the mean of 20,032 f32 terms: the sum's last bits
        assert abs(forest["logloss"] - one["logloss"]) < 1e-6
    over = {k: (got["numbers"][k], lim)
            for k, lim in _cfg()["limits"].items()
            if not got["numbers"][k] <= lim}
    assert not over, (over, got["numbers"])
    assert got["numbers"]["subset_split_share"] > 0.5


@pytest.mark.parametrize("shards", [1, 4])
def test_psum_bytes_are_the_static_sum_on_a_mesh_and_0_on_one_device(
        cl, shards):
    got = _fit(shards, SEED)
    assert got["nbins"] == NBINS
    ntrees, depth = (int(_cfg()["params"][k])
                     for k in ("ntrees", "max_depth"))
    a_tree = device_tree.psum_bytes(depth, NBINS, shards)
    if shards == 1:
        assert a_tree == dict.fromkeys(SITES, 0)
        walk = 0
    else:
        # 896 lanes x 3 sums x (1 + 2 + ... + 512) slots, 2,048 x 4 leaf
        # sums, the two scalars of the mean: f32
        assert a_tree == {"hist": 4 * 896 * 3 * 1023,
                          "leaf_sums": 4 * 2048 * 4, "stats": 8}
        walk = 4 * (3 + 2 * 400)    # log loss, se, weights; the AUC's bins
    assert got["psum"] == {s: float(ntrees * n) for s, n in a_tree.items()
                           if n}       # a site that moved by 0 is left out
    trees, scored = got["attrs"]["trees"], got["attrs"]["metrics"]
    # a leaf pass a tree, 2,048 slots of four f32 sums on the MXU as 8 x 256,
    # whether the rows lie on one device or four
    assert got["leaf"] == {"matmul_split": float(ntrees)}
    assert trees["leaf_lowering"] == "matmul_split"
    assert trees["shards"] == scored["shards"] == shards
    assert trees["psum_bytes"] == ntrees * sum(a_tree.values())
    assert scored["psum_bytes"] == walk

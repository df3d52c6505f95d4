"""The airline on-time GBM (bench/configs/airline_gbm_d10.json: 6 enum + 2
numeric columns, depth 10, 100 bins) against the plain reference that knows
subset splits (bench/reference/gbm_enum.py), at 20,000 rows on the CPU mesh,
under the configuration's own limits; the planted faults and the
lower-precision control, which must each fail a limit; the forest reader;
and the counters and span attributes the shape brought. Counts and
correctness only, never a time."""

import json
import os
import urllib.parse
import urllib.request

import numpy as np
import pytest

from bench.harness import data_airline as recipe
from bench.harness import forest_enum
from bench.reference import gbm_enum
from h2o3_tpu.obs import metrics, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 20_032        # 20,000 rounded up to tile the 8-device test mesh
NA_VARIANT = 1.0 / 256      # the recipe's variant with missing rows
SEEDS = (3_000_000_101, 3_000_000_102, 3_000_000_103)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "bench", "configs",
                           "airline_gbm_d10.json")) as f:
        return json.load(f)


class _Sys:
    """What forest_enum asks of bench.harness.system.System."""

    def __init__(self, cl):
        import h2o3_tpu

        self.h2o, self.cluster = h2o3_tpu, cl

    def _check_rows(self, n):
        assert self.cluster.pad_rows(n) == n

    def model(self, model_id):
        from h2o3_tpu.core.dkv import DKV

        return DKV.get(model_id)


def _install(cl, seed, key, na_share=None):
    out = recipe.device_columns(seed, ROWS, sharding=cl.row_sharding(),
                                na_share=na_share)
    forest_enum.install_training_frame(
        _Sys(cl), key, recipe.frame_columns(), out[:-1], out[-1],
        recipe.RESPONSE_NAME, recipe.RESPONSE_DOMAIN)
    return out[:-1], out[-1]


def _judge(cols, y, cfg, model):
    produced = forest_enum.forest_arrays(model.forest, model.spec)
    produced["logloss"] = float(model._output.training_metrics.logloss)
    numbers = gbm_enum.check_forest(cols, y, cfg, produced,
                                    k_follow=int(cfg["k_follow"]))
    over = {k: (numbers[k], lim) for k, lim in cfg["limits"].items()
            if not numbers[k] <= lim}
    return numbers, over


def _counter(name):
    m = metrics.REGISTRY.get(name)
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in m.snapshot()["samples"] if s["labels"]}


def _delta(before, after):
    return {dict(k).popitem()[1]: v - before.get(k, 0.0)
            for k, v in after.items() if v != before.get(k, 0.0)}


@pytest.fixture(scope="module")
def rest(cl):
    from h2o3_tpu.api.server import start_server

    srv = start_server(port=0)
    base = f"http://127.0.0.1:{srv.port}"

    def call(method, path, **body):
        data = urllib.parse.urlencode(body).encode() if body else None
        req = urllib.request.Request(base + path, data=data, method=method)
        with urllib.request.urlopen(req, timeout=600) as r:
            return json.loads(r.read())

    yield call
    srv.stop()


@pytest.mark.parametrize("seed,path", [(SEEDS[0], "builder"),
                                       (SEEDS[1], "rest"),
                                       (SEEDS[2], "builder_na")])
def test_program_against_the_reference_under_the_cells_limits(cl, cfg, rest,
                                                              seed, path):
    from h2o3_tpu.core.dkv import DKV
    from h2o3_tpu.models.tree.gbm import GBM

    key = f"airline_ref_{seed}.hex"
    # one case on the recipe's variant with missing rows: the missing bin
    # of a histogram and the side a split gives it, held to the reference
    cols, y = _install(cl, seed, key,
                       na_share=NA_VARIANT if path == "builder_na" else None)
    if path != "rest":
        model = GBM(seed=1, **cfg["params"]).train(
            y=recipe.RESPONSE_NAME, training_frame=DKV.get(key))
    else:
        import time

        out = rest("POST", "/3/ModelBuilders/gbm", training_frame=key,
                   response_column=recipe.RESPONSE_NAME,
                   model_id=f"airline_ref_{seed}", seed=1,
                   **{k: json.dumps(v) if isinstance(v, bool) else v
                      for k, v in cfg["params"].items()})
        job = out["job"]["key"]["name"]
        while True:
            st = rest("GET", f"/3/Jobs/{job}")["jobs"][0]
            if st["status"] in ("DONE", "FAILED", "CANCELLED"):
                break
            time.sleep(0.05)
        assert st["status"] == "DONE", st
        model = DKV.get(f"airline_ref_{seed}")
    numbers, over = _judge(cols, y, cfg, model)
    assert not over, (over, numbers)
    assert numbers["subset_split_share"] > 0.5
    assert str(model.spec.bin_columns(DKV.get(key)).dtype) == "int16"


@pytest.fixture(scope="module")
def rows():
    """The cell's frame, and the variant of it that has missing values (the
    only one on which the side a split gives them can be judged)."""
    out = recipe.device_columns(3_000_000_111, ROWS)
    na = recipe.device_columns(3_000_000_111, ROWS, na_share=NA_VARIANT)
    return {False: (out[:-1], out[-1]), True: (na[:-1], na[-1])}


@pytest.mark.parametrize("label,must_fail", [
    ("reference", None), ("stated", None),
    ("control", {"edge_gap", "init_gap", "split_gain_gap"}),
    ("code_order", {"split_gain_loss", "split_gain_gap"}),
    ("na_flipped", {"cover_gap", "split_gain_gap"}),
    ("level_dropped", {"cover_gap"}),
    ("state_unchanged", {"leaf_gap", "logloss_gap", "split_gain_gap"}),
    ("depth_cut", {"split_rule_breaks"}),
    ("min_rows_ignored", {"split_rule_breaks"}),
])
def test_control_and_planted_faults_fail_a_limit(rows, cfg, label, must_fail):
    cols, y = rows[label == "na_flipped"]
    k = int(cfg["k_follow"])
    kw = ({"precision": label} if label in gbm_enum.PRECISIONS
          else {"fault": label})
    forest = gbm_enum.grow(cols, y, cfg, ntrees=k, **kw)
    numbers = gbm_enum.check_forest(cols, y, cfg, forest, k_follow=k)
    over = {n for n, lim in cfg["limits"].items() if not numbers[n] <= lim}
    if must_fail is None:
        assert not over, numbers
    else:       # every limit named, not just one of them
        assert must_fail <= over, (over, numbers)


def test_forest_reader_round_trips_a_subset_split():
    """A hand-built forest of one subset split, one threshold split and
    three leaves comes back as (feature, set of left levels or threshold,
    side of the missing bin), the set cut to the feature's level count."""
    from h2o3_tpu.models.tree.binning import BinSpec
    from h2o3_tpu.models.tree.compressed import CompressedForest
    from h2o3_tpu.models.tree.dtree import HostTree, Split

    edges = np.array([1.5, 2.5, 7.0], np.float32)
    spec = BinSpec(["g", "x"], [True, False], [6, len(edges) + 2],
                   [np.zeros(0, np.float32), edges], [5, 0])
    left = np.array([True, False, False, True, False])
    tree = HostTree()
    a, b = tree.new_node(1), tree.new_node(1)
    c, d = tree.new_node(2), tree.new_node(2)
    tree.nodes[0].split = Split(0, True, -1, left, True, 3.0, (0, 0), (0, 0))
    tree.nodes[0].left, tree.nodes[0].right = a, b
    tree.nodes[b].split = Split(1, False, 1, None, False, 1.0, (0, 0), (0, 0))
    tree.nodes[b].left, tree.nodes[b].right = c, d
    for nid, v in ((a, 0.5), (c, -0.25), (d, 0.125)):
        tree.nodes[nid].leaf_value = v
    for n, w in zip(tree.nodes, (100, 40, 60, 25, 35)):
        n.weight = w
    fo = CompressedForest.from_host_trees([tree], spec, max_depth=2,
                                          init_f=-1.5)
    got = forest_enum.forest_arrays(fo, spec)
    assert got["cat_split"][0].tolist() == [0, -1, -1, -1, -1]
    assert len(got["cat_rows"]) == 1
    assert got["cat_rows"][0].tolist() == left.tolist()       # 5, not maxB
    assert got["na_left"][0, 0] and not got["na_left"][0, b]
    assert got["thr"][0, b] == np.float32(2.5) and got["feat"][0, b] == 1
    assert got["left"][0, 0] == a and got["right"][0, b] == d
    assert got["leaf"][0].tolist() == [0.0, 0.5, 0.0, -0.25, 0.125]
    assert got["cover"][0].tolist() == [100, 40, 60, 25, 35]
    assert got["is_cat"].tolist() == [True, False]
    assert got["levels"].tolist() == [5, 0] and got["init_f"] == -1.5
    assert fo.walk_form == "select+cat"


@pytest.fixture(scope="module")
def traced_and_plain(cl, cfg):
    """The same fit twice, without and with an active trace, and what the
    counters moved by each time."""
    from h2o3_tpu.core.dkv import DKV
    from h2o3_tpu.models.tree.gbm import GBM

    key = "airline_ref_counters.hex"
    _install(cl, 3_000_000_121, key)
    names = ("h2o3_tree_splits_total",
             "h2o3_forest_walk_total", "h2o3_tree_trees_built_total",
             "h2o3_backend_compiles_total")

    def fit():
        before = {n: _counter(n) for n in names}
        m = GBM(seed=1, **cfg["params"]).train(
            y=recipe.RESPONSE_NAME, training_frame=DKV.get(key))
        return m, {n: _delta(before[n], _counter(n)) for n in names}

    fit()                                   # compiles happen here
    plain, moved_plain = fit()
    with tracing.root_span("ingress", path="/3/ModelBuilders/gbm") as root:
        traced, moved_traced = fit()
    spans = tracing.get_trace(root.span["trace_id"], include_remote=False)
    return plain, moved_plain, traced, moved_traced, spans


@pytest.mark.parametrize("what", ["splits", "walk",
                                  "trace_changes_nothing", "span_attrs"])
def test_counters_and_spans_of_an_enum_fit(traced_and_plain, cfg, what):
    plain, moved, traced, moved_traced, spans = traced_and_plain
    fo = plain.forest
    internal = np.asarray(fo.feat) >= 0
    enum = int(np.count_nonzero(np.asarray(fo.cat_split)[internal] >= 0))
    if what == "splits":
        # the counter equals a count of the tables the fit fetched
        assert moved["h2o3_tree_splits_total"] == {
            "enum": enum, "numeric": int(internal.sum()) - enum}
        assert enum > internal.sum() / 2
    elif what == "walk":
        # the widest read of the walk is depth 9's: 512 subsets of ten
        # packed words, past _SELECT_MAX_NODES
        assert fo.walk_form == "gather+cat"
        assert set(moved["h2o3_forest_walk_total"]) == {"gather+cat"}
    elif what == "trace_changes_nothing":
        assert moved_traced == moved
        assert not moved.get("h2o3_backend_compiles_total")
        for a, b in zip(fo.arrays(), traced.forest.arrays()):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    else:
        by_name = {s["name"]: s["attrs"] for s in spans}
        assert by_name["bin"]["bin_dtype"] == "int16"
        assert by_name["bin"]["max_bins"] == 301
        assert by_name["assemble"]["enum_splits"] == enum
        assert by_name["assemble"]["nodes"] == \
            2 * int(internal.sum()) + fo.n_trees
        # the metrics pass walks two trees of ten levels; depth 9's packed
        # words are gathered in each
        assert by_name["metrics"]["walk_levels"] == 20
        assert by_name["metrics"]["walk_gather_levels"] == 2

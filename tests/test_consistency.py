"""Tier-1 consistency guards, now backed by ONE invariant engine.

ISSUE 11 folded the four text guards that grew here across PRs 4-9
(faultpoint names, metric registry, timeline kinds, env-knob docs) into
``h2o3_tpu/analysis`` — a multi-pass static analyzer that also checks the
invariants those guards could not reach: mirrored-program divergence,
lock ordering, raw unpickling, compat routing and span sync hygiene.

This module is the tier-1 wiring:

1. the FULL analyzer must exit clean on the repo (zero non-baselined
   findings, zero baseline-hygiene problems) inside its 10 s budget —
   this single test carries the mirrored/lock/serialization/compat/sync
   invariants plus the four folded registry guards;
2. the registry passes also run individually so a drift failure names
   the offending pass directly instead of a wall of findings;
3. the guards that need live behavior stay here as tests: pytest-marker
   registry sync, the live metrics registry agreeing with the source
   scan, rapids fusibility declarations, the genmodel import firewall,
   and the sharded-data-plane ``gathered_rows`` smoke (the one non-text
   guard; conftest routes it to the heavy tail).

All text passes are stdlib-ast scans — no devices, milliseconds to
single-digit seconds.
"""

import re
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
SRC = ROOT / "h2o3_tpu"
TESTS = ROOT / "tests"

# pytest's own marks + common third-party ones: not ours to declare
_BUILTIN_MARKS = {"parametrize", "skip", "skipif", "xfail", "usefixtures",
                  "filterwarnings", "timeout"}


def _py_sources(root):
    for p in sorted(root.rglob("*.py")):
        if "__pycache__" in p.parts:
            continue
        yield p, p.read_text(encoding="utf-8", errors="replace")


# ---------------------------------------------------------------------------
# the invariant engine (h2o3_tpu/analysis) — tier-1 wiring
# ---------------------------------------------------------------------------

def test_static_analyzer_clean_within_budget():
    """``python -m h2o3_tpu.analysis`` equivalent: every pass over the
    whole repo, all findings either fixed or baselined-with-justification,
    and the full run inside the 10 s budget the issue pins."""
    from h2o3_tpu import analysis

    t0 = time.perf_counter()
    new, baselined, problems = analysis.run_repo(root=ROOT)
    dt = time.perf_counter() - t0
    assert not new, (
        "static analyzer found NEW invariant violations (fix them, or — "
        "sync-hygiene/compat-routing only — baseline with a "
        "justification):\n" + "\n".join(f.render() for f in new))
    assert not problems, (
        "baseline hygiene problems:\n"
        + "\n".join(f.render() for f in problems))
    assert dt < 10.0, (
        f"analyzer took {dt:.1f}s — the tier-1 budget is 10s; a pass "
        f"grew superlinear (check call-graph closure caching)")


@pytest.fixture(scope="module")
def actx():
    """One parsed-project context shared by the per-pass guards (the
    call-graph build dominates a pass run)."""
    from h2o3_tpu import analysis

    return analysis.make_context(ROOT)


@pytest.mark.parametrize("pass_name", ["faultpoints", "metric-registry",
                                       "timeline-kinds", "knob-docs",
                                       "compile-ledger"])
def test_registry_guard_pass(actx, pass_name):
    """The folded consistency guards (plus the ISSUE-12 compile-ledger
    chokepoint), one pass each, so drift failures name the responsible
    registry directly. (Covered by the full run above too — this is the
    readable failure mode.)"""
    from h2o3_tpu import analysis

    findings = analysis.run(actx, [pass_name])
    assert not findings, "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# guards that need live behavior (not expressible as text passes)
# ---------------------------------------------------------------------------

def _declared_markers():
    text = (ROOT / "pyproject.toml").read_text()
    m = re.search(r"markers\s*=\s*\[(.*?)\]", text, re.S)
    assert m, "pyproject.toml has no [tool.pytest.ini_options] markers list"
    # each entry is "name: description" — take the leading identifier
    # (descriptions may contain nested quotes/colons/parens)
    return set(re.findall(r"['\"]\s*([A-Za-z_]\w*)\s*:", m.group(1)))


def _used_markers():
    used = set()
    for _p, text in _py_sources(TESTS):
        used |= set(re.findall(r"pytest\.mark\.(\w+)", text))
    return used - _BUILTIN_MARKS


def test_pyproject_markers_match_test_usage():
    declared = _declared_markers()
    used = _used_markers()
    undeclared = used - declared
    assert not undeclared, (
        f"marker(s) {sorted(undeclared)} are used under tests/ but not "
        "declared in pyproject.toml [tool.pytest.ini_options] markers — "
        "--strict-markers runs will fail")
    unused = declared - used
    assert not unused, (
        f"marker(s) {sorted(unused)} are declared in pyproject.toml but "
        "never used under tests/ — drop them or mark the tests")


def test_genmodel_runner_has_no_training_imports():
    """The standalone runtimes under ``h2o3_genmodel/`` must stay loadable
    without the framework: any ``import h2o3_tpu`` there would silently
    re-couple the dependency-free scoring artifact to the training
    stack."""
    offenders = []
    for p, text in _py_sources(ROOT / "h2o3_genmodel"):
        for mm in re.finditer(
                r"^\s*(?:import\s+h2o3_tpu|from\s+h2o3_tpu)", text, re.M):
            line = text[: mm.start()].count("\n") + 1
            offenders.append(f"{p.relative_to(ROOT)}:{line}")
    assert not offenders, (
        f"h2o3_genmodel imports the training stack at {offenders} — the "
        "standalone runners must depend on numpy/stdlib (+ jax for AOT) "
        "only")


def test_live_metric_registry_agrees_with_source_scan():
    """Behavioral half of the metric-registry pass: every metric the
    text scan sees is present in the LIVE registry after import
    (conditional registration would hide a series from /3/Metrics).
    Uses the PASS'S OWN pattern so the two halves cannot drift."""
    from h2o3_tpu.analysis.passes_registries import METRIC_REG_PAT

    names = set()
    for _p, text in _py_sources(SRC):
        names |= set(METRIC_REG_PAT.findall(text))
    assert names, "no metric registrations found under h2o3_tpu/"
    from h2o3_tpu.obs import metrics as obs_metrics

    live = set(obs_metrics.REGISTRY.names())
    missing = names - live
    assert not missing, (
        f"metric(s) {sorted(missing)} are registered in source but absent "
        "from the live registry (conditional registration?)")


def test_rapids_prims_declare_fusibility_class():
    """ISSUE-10 guard (mirrors the timeline-KINDS guard): every registered
    Rapids prim must carry exactly one fusibility class from the closed
    enumeration {fusible, barrier, host} in rapids/fusion.PRIM_FUSION —
    a new prim without a declaration would silently land as an un-fused
    barrier the planner (and the barrier_fallbacks metric) cannot see.
    Dead classifications (names no prim registers) are drift too."""
    from h2o3_tpu.rapids import fusion
    from h2o3_tpu.rapids.eval import PRIMS

    registered = set(PRIMS)
    classified = set(fusion.PRIM_FUSION)
    missing = registered - classified
    assert not missing, (
        f"rapids prim(s) {sorted(missing)} are registered but declare no "
        "fusibility class — add them to rapids/fusion.py (fusible / "
        "barrier / host); unclassified prims can't be planned or counted")
    dead = classified - registered
    assert not dead, (
        f"fusibility class entries {sorted(dead)} name prims that are no "
        "longer registered — drop them from rapids/fusion.py")
    bad = {n: c for n, c in fusion.PRIM_FUSION.items()
           if c not in fusion.FUSION_CLASSES}
    assert not bad, f"fusibility classes outside the enumeration: {bad}"
    # the planner's root set must be a subset of the fusible class
    assert fusion.ROOT_OPS <= {n for n, c in fusion.PRIM_FUSION.items()
                               if c == fusion.FUSIBLE}
    # the LAZY session planner's deferral surface: fusible roots plus the
    # two device barrier prims it models as DAG nodes — a reclassification
    # of either would silently change what defers
    for nm in ("sort", "rows"):
        assert fusion.PRIM_FUSION.get(nm) == fusion.BARRIER, (
            f"rapids/planner.py defers {nm!r} statements as device DAG "
            f"nodes; it must stay barrier-class, got "
            f"{fusion.PRIM_FUSION.get(nm)!r}")
    # the newly device-resident prims must never regress to host class
    # (their device paths are the lazy-session PR's acceptance surface)
    for nm in ("rank_within_groupby", "difflag1"):
        assert fusion.PRIM_FUSION.get(nm) == fusion.BARRIER, (
            f"{nm!r} is device-resident (ops/window.py); host class would "
            f"misreport it as a barrier_fallbacks exceptional path")


def test_fused_paths_never_gather_columns_to_coordinator():
    """ISSUE-7 guard: the fused scoring path and the tree-training input
    path must build their inputs from addressable row shards in place.
    Train a tiny GBM on the virtual 8-device mesh and score it through
    the fused session: the per-process ``gathered_rows`` counter (the one
    ``GET /3/ScoringMetrics`` serves under ``data_plane``) must not move,
    while ``packed_rows`` covers both the training bin pack and the
    scored request. A regression that re-introduces a coordinator column
    fetch anywhere under either path trips this immediately."""
    import numpy as np

    import h2o3_tpu
    from h2o3_tpu import scoring
    from h2o3_tpu.core import sharded_frame
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.models.tree.gbm import GBM

    h2o3_tpu.init()
    rng = np.random.default_rng(77)
    n = 512
    fr = Frame()
    x = rng.standard_normal(n)
    fr.add("x1", Column.from_numpy(x))
    fr.add("g", Column.from_numpy(
        np.array(["a", "b"])[rng.integers(0, 2, n)], ctype="enum"))
    fr.add("y", Column.from_numpy(
        np.where(rng.random(n) < 1 / (1 + np.exp(-x)), "Y", "N"),
        ctype="enum"))
    before = sharded_frame.counters()
    model = GBM(ntrees=2, max_depth=2, seed=7).train(
        y="y", training_frame=fr)
    sfr = Frame()
    sfr.add("x1", Column.from_numpy(rng.standard_normal(100)))
    sfr.add("g", Column.from_numpy(
        np.array(["a", "b"])[rng.integers(0, 2, 100)], ctype="enum"))
    scoring.ScoringSession(model).predict(sfr)
    after = sharded_frame.counters()
    assert after["gathered_rows"] == before["gathered_rows"], (
        "a fused scoring / tree input call site pulled full columns to "
        "the coordinator host (gathered_rows moved) — the sharded data "
        "plane contract is broken")
    assert after["packed_rows"] >= before["packed_rows"] + n + 100


def test_ingest_never_stages_whole_columns_on_coordinator(tmp_path):
    """ISSUE-15 guard (the ingest-side gathered_rows contract): a CSV
    import must ride the chunked sharded pipeline — every chunk's rows
    land directly in their owning row shard — and the whole
    import→train→score arc must leave ``coordinator_ingest_bytes``
    untouched. A regression that re-introduces the one-gather-at-the-
    coordinator assembly (the pre-ISSUE-15 docstring's own words) trips
    this immediately."""
    import numpy as np

    import h2o3_tpu
    from h2o3_tpu import scoring
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.ingest import chunked
    from h2o3_tpu.models.tree.gbm import GBM

    h2o3_tpu.init()
    rng = np.random.default_rng(99)
    n = 600
    p = tmp_path / "smoke.csv"
    with open(p, "w") as f:
        f.write("x1,g,y\n")
        for i in range(n):
            x = rng.normal()
            f.write(f"{x:.6f},{'ab'[i % 2]},{'Y' if x > 0 else 'N'}\n")
    before = chunked.counters()
    fr = h2o3_tpu.import_file(str(p), destination_frame="ingest_smoke")
    model = GBM(ntrees=2, max_depth=2, seed=5).train(
        y="y", training_frame=fr)
    sfr = Frame()
    sfr.add("x1", Column.from_numpy(rng.standard_normal(64)))
    sfr.add("g", Column.from_numpy(
        np.array(["a", "b"])[rng.integers(0, 2, 64)], ctype="enum"))
    scoring.ScoringSession(model).predict(sfr)
    after = chunked.counters()
    assert after["coordinator_ingest_bytes"] == \
        before["coordinator_ingest_bytes"], (
        "import→train→score staged whole ingest columns on the "
        "coordinator host — the chunked sharded ingest contract is "
        "broken")
    assert after["chunk_rows"] >= before["chunk_rows"] + n
    fr.delete()


def test_multi_entry_flush_is_one_dispatch_per_entry_window():
    """ISSUE-13 guard, as ISSUE 37 left it: a multi-entry micro-batch
    flush on the sharded path runs ONE window loop in which each entry is
    windowed on its own rows (one dispatch for an entry that fits a
    bucket) with ``gathered_rows`` untouched, and a second combination of
    row counts compiles nothing. A regression to a host gather, or to an
    eager op shaped by the combination, trips this immediately."""
    import numpy as np

    import h2o3_tpu
    from h2o3_tpu import scoring
    from h2o3_tpu.core import sharded_frame
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.models.tree.gbm import GBM

    h2o3_tpu.init()
    rng = np.random.default_rng(88)
    n = 512
    fr = Frame()
    x = rng.standard_normal(n)
    fr.add("x1", Column.from_numpy(x))
    fr.add("y", Column.from_numpy(
        np.where(rng.random(n) < 1 / (1 + np.exp(-x)), "Y", "N"),
        ctype="enum"))
    model = GBM(ntrees=2, max_depth=2, seed=8).train(
        y="y", training_frame=fr)

    def score_fr(m, seed):
        sfr = Frame()
        sfr.add("x1", Column.from_numpy(
            np.random.default_rng(seed).standard_normal(m)))
        return sfr

    from h2o3_tpu.obs import metrics

    def compiled():
        return sum(x["value"] for x in metrics.REGISTRY.get(
            "h2o3_backend_compiles_total").snapshot()["samples"])

    sess = scoring.ScoringSession(model)
    frames = [score_fr(40 + 13 * i, 100 + i) for i in range(4)]
    sess.predict(frames[0])                 # warm the one bucket involved
    sess.predict_batch([(f, None, False) for f in frames])
    before = sharded_frame.counters()
    scoring.reset_dispatch_counters()
    c0 = compiled()
    sess.predict_batch([(f, None, False) for f in frames[::-1]])
    dc = scoring.dispatch_counters()
    after = sharded_frame.counters()
    assert dc.get("sharded") == 4, (
        f"a 4-entry flush recorded {dc} fused dispatches — one window "
        "an entry is the contract")
    assert compiled() == c0, "a new combination of row counts compiled"
    assert after["gathered_rows"] == before["gathered_rows"], (
        "the coalesced flush gathered columns to the coordinator host")


def test_pipeline_splice_is_one_program_per_bucket_with_zero_gathers():
    """ISSUE-16 guard: a 3-statement lazy Rapids feature chain feeding a
    GBM predict must run as EXACTLY ONE ``pipeline``-family fused program
    for its row bucket — engineered Columns never materialize
    (``materialized_columns`` stays 0) and ``gathered_rows`` never moves.
    A regression that re-materializes the munge output (or re-splits the
    dispatch) trips this immediately."""
    import numpy as np

    import h2o3_tpu
    from h2o3_tpu import pipeline, scoring
    from h2o3_tpu.core import sharded_frame
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.models.tree.gbm import GBM
    from h2o3_tpu.obs import compiles
    from h2o3_tpu.rapids import Session, exec_rapids, fusion, planner

    h2o3_tpu.init()
    rng = np.random.default_rng(66)
    n = 500
    tr = Frame()
    x = rng.standard_normal(n)
    tr.add("x1", Column.from_numpy(x))
    tr.add("x2", Column.from_numpy(rng.standard_normal(n)))
    tr.add("y", Column.from_numpy(
        np.where(rng.random(n) < 1 / (1 + np.exp(-x)), "Y", "N"),
        ctype="enum"))
    model = GBM(ntrees=2, max_depth=2, seed=6).train(
        y="y", training_frame=tr)
    m = 300
    raw = Frame(key="consist_pipe_raw")
    raw.add("r1", Column.from_numpy(rng.standard_normal(m)))
    raw.add("r2", Column.from_numpy(rng.standard_normal(m)))
    raw.install()
    with planner.force(True), fusion.force(True), pipeline.force(True):
        s = Session("consist_pipe")
        # split-free 3-statement chain: one fused program, no sub-plans
        exec_rapids('(tmp= cp_a (+ (cols consist_pipe_raw [0]) 1))', s)
        exec_rapids('(tmp= cp_b (ifelse (> (cols consist_pipe_raw [1]) 0) '
                    '(cols consist_pipe_raw [1]) cp_a))', s)
        pf = exec_rapids('(tmp= cp_pf (colnames= (cbind cp_a cp_b) [0 1] '
                         '["x1" "x2"]))', s)
        rows_before = [r for r in compiles.ledger_rows()
                       if r["family"] == "pipeline"]
        gath_before = sharded_frame.counters()["gathered_rows"]
        pcount_before = pipeline.counters()
        scoring.session_for(model).predict(pf, key="consist_pipe_out")
        rows = [r for r in compiles.ledger_rows()
                if r["family"] == "pipeline"][len(rows_before):]
        pcount = pipeline.counters()
        s.end()
    assert len(rows) == 1, (
        f"a 3-statement chain + predict landed {len(rows)} pipeline "
        "ledger rows for its one row bucket — the one-program-per-bucket "
        "contract is broken")
    assert rows[0]["cache"] == "compile"
    assert pcount["fused_dispatches"] == \
        pcount_before["fused_dispatches"] + 1
    assert pcount["materialized_columns"] == \
        pcount_before["materialized_columns"], (
        "the fused munge→score path materialized an engineered Column")
    assert sharded_frame.counters()["gathered_rows"] == gath_before, (
        "the fused munge→score path gathered columns to the coordinator")
    model.delete()

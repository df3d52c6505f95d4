"""Flagship benchmark: GBM training throughput (the north-star metric,
BASELINE.md: 'GBM rows/sec/chip').

Synthetic airlines-shaped task: mixed numeric + categorical predictors,
binary response. Throughput counts every row visited across all trees
(rows × ntrees / wallclock), the standard hist-GBM accounting.
"""

from __future__ import annotations

import time

import numpy as np


def arm_stage_autopsy() -> bool:
    """Bench autopsy (ISSUE 8): when ``H2O3_BENCH_STAGE_TIMEOUT_S`` names
    the time limit this stage runs under (``timeout N python -m
    h2o3_tpu.bench``), arm a daemon timer that — a few seconds before the
    kill lands — dumps a flight record (timeline ring + metrics snapshot)
    and prints one ``H2O3_FLIGHT_JSON {...}`` line to stderr with the
    record path and the last 20 timeline events, so a timed-out device
    stage says WHERE it died. Returns True when a timer was armed."""
    import json as _json
    import os as _os
    import sys as _sys
    import threading as _th

    try:
        t = float(_os.environ.get("H2O3_BENCH_STAGE_TIMEOUT_S") or 0)
    except ValueError:
        return False
    if t <= 6:
        return False

    def dump():
        try:
            from h2o3_tpu.obs import flight as _fl
            from h2o3_tpu.obs import phases as _ph
            from h2o3_tpu.utils import timeline as _tl

            report = _ph.phase_report()
            wedged = _ph.wedged_phase()
            path = _fl.record_flight(
                "bench_stage_timeout",
                extra={"stage_timeout_s": t, "phase_report": report,
                       "wedged_phase": wedged})
            print("H2O3_FLIGHT_JSON " + _json.dumps(
                {"flight_record": path, "timeline_tail": _tl.events(20),
                 "phase_report": report,
                 **({"phase": wedged} if wedged else {})},
                default=str), file=_sys.stderr, flush=True)
        except Exception:   # noqa: BLE001 — the autopsy must never be the
            pass            # thing that kills a healthy stage

    tm = _th.Timer(max(t - 5.0, 1.0), dump)
    tm.daemon = True
    tm.start()
    return True


def run_flagship(n_rows: int = 1_000_000, n_num: int = 8, n_cat: int = 2,
                 ntrees: int = 20, max_depth: int = 5):
    import h2o3_tpu
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.models.tree.gbm import GBM

    h2o3_tpu.init()
    rng = np.random.default_rng(0)
    fr = Frame()
    logit = np.zeros(n_rows)
    for i in range(n_num):
        x = rng.standard_normal(n_rows)
        logit += x * rng.uniform(-1, 1)
        fr.add(f"n{i}", Column.from_numpy(x))
    doms = [np.array(["a", "b", "c", "d"]), np.array(["x", "y", "z"])]
    for i in range(n_cat):
        codes = rng.integers(0, len(doms[i % 2]), n_rows)
        logit += (codes - 1) * 0.3
        fr.add(f"c{i}", Column.from_numpy(doms[i % 2][codes], ctype="enum"))
    y = np.where(rng.random(n_rows) < 1 / (1 + np.exp(-logit)), "Y", "N")
    fr.add("y", Column.from_numpy(y, ctype="enum"))

    # warm the jit caches with a tiny run (compile time excluded, as the
    # reference's JVM warms up before its measured passes)
    GBM(ntrees=2, max_depth=max_depth).train(y="y", training_frame=fr)

    t0 = time.perf_counter()
    GBM(ntrees=ntrees, max_depth=max_depth).train(y="y", training_frame=fr)
    dt = time.perf_counter() - t0
    _print_hist_aux()
    return n_rows * ntrees / dt, "gbm_rows_per_sec"


def _print_hist_aux():
    """Which histogram lowering the timed train actually ran, plus its
    frontier tile width — so a device round's corpse (or number) says
    which path produced it. Values are numeric (the driver floats every
    H2O3_BENCH line): hist_lowering is the LOWERINGS index."""
    from h2o3_tpu.models.tree import pallas_hist

    rep = pallas_hist.hist_report()
    print(f"H2O3_BENCH hist_lowering "
          f"{pallas_hist.lowering_code(rep['lowering'])}", flush=True)
    print(f"H2O3_BENCH hist_tile_S {rep['tile_S']}", flush=True)


def run_drf_deep(n_rows: int = 200_000, ntrees: int = 5,
                 max_depth: int = 20):
    """Secondary metric: depth-20 DRF (the dense-frontier deep grower) —
    rows × trees / wallclock, recorded alongside the flagship."""
    import h2o3_tpu
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.models.tree.drf import DRF

    h2o3_tpu.init()
    rng = np.random.default_rng(1)
    fr = Frame()
    logit = np.zeros(n_rows)
    for i in range(6):
        x = rng.standard_normal(n_rows)
        logit += x * rng.uniform(-1, 1)
        fr.add(f"n{i}", Column.from_numpy(x))
    y = np.where(rng.random(n_rows) < 1 / (1 + np.exp(-logit)), "Y", "N")
    fr.add("y", Column.from_numpy(y, ctype="enum"))
    DRF(ntrees=1, max_depth=max_depth, seed=1).train(
        y="y", training_frame=fr)            # warm compile
    t0 = time.perf_counter()
    DRF(ntrees=ntrees, max_depth=max_depth, seed=1).train(
        y="y", training_frame=fr)
    dt = time.perf_counter() - t0
    _print_hist_aux()
    return n_rows * ntrees / dt, "drf_deep_rows_per_sec"


def run_compile_probe(n_rows: int = 20_000):
    """Compile-only stage: the flagship program on tiny rows. Wallclock here
    is compile-dominated — it tells 'slow compile' from 'slow execute'."""
    t0 = time.perf_counter()
    run_flagship(n_rows=n_rows, ntrees=2)
    return time.perf_counter() - t0, "gbm_compile_secs"


def run_scoring(train_rows: int = 20_000, ntrees: int = 10,
                max_depth: int = 5, passes: int = 3):
    """Serving fast-path metric: bucketed batched scoring throughput
    (rows/sec) through scoring.ScoringSession — the compile-once device
    path behind POST /3/Predictions. Mixed request sizes exercise several
    row buckets; the warm pass excludes per-bucket compiles, matching the
    flagship's warm-up convention."""
    import h2o3_tpu
    from h2o3_tpu import scoring
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.models.tree.gbm import GBM

    h2o3_tpu.init()
    rng = np.random.default_rng(2)

    def make(n, with_y):
        fr = Frame()
        logit = np.zeros(n)
        for i in range(6):
            x = rng.standard_normal(n)
            logit += x * ((-1) ** i) * 0.5
            fr.add(f"n{i}", Column.from_numpy(x))
        codes = rng.integers(0, 4, n)
        fr.add("c0", Column.from_numpy(
            np.array(["a", "b", "c", "d"])[codes], ctype="enum"))
        if with_y:
            yy = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)), "Y", "N")
            fr.add("y", Column.from_numpy(yy, ctype="enum"))
        return fr

    model = GBM(ntrees=ntrees, max_depth=max_depth, seed=3).train(
        y="y", training_frame=make(train_rows, True))
    sess = scoring.session_for(model)
    sizes = [777, 3_000, 12_000, 16_384]
    frames = [make(s, False) for s in sizes]
    for fr in frames:                      # warm every bucket once
        sess.predict(fr)
    from h2o3_tpu.core import sharded_frame
    import jax

    sharded_frame.reset_counters()         # scope counters to the timed run
    t0 = time.perf_counter()
    rows = 0
    for _ in range(passes):
        for fr in frames:
            sess.predict(fr)
            rows += fr.nrows
    dt = time.perf_counter() - t0
    # sharded-data-plane evidence next to the throughput number: the fused
    # metric must come from per-process shard packing (gathered_rows == 0
    # on the sharded path; the /3/ScoringMetrics data_plane block reports
    # the same counters)
    dp = sharded_frame.counters()
    print(f"H2O3_BENCH score_devices {len(jax.devices())}", flush=True)
    print(f"H2O3_BENCH score_packed_rows {dp['packed_rows']}", flush=True)
    print(f"H2O3_BENCH score_gathered_rows {dp['gathered_rows']}",
          flush=True)

    # -- coalesced-flush phase (ISSUE 13): concurrent small requests
    # through the micro-batcher; the dispatch counters assert that a
    # multi-entry flush costs ~ONE fused dispatch per bucket (the PR-7
    # per-entry trade-off, removed) and the session p99 rides along for
    # the SLO-admission trajectory
    import os as _os
    import threading as _threading

    try:
        conc = int(_os.environ.get("H2O3_BENCH_SCORE_CONCURRENCY", "16"))
    except ValueError:
        conc = 16
    small = [make(128, False) for _ in range(max(conc, 2))]
    sess.predict(small[0])                 # warm the small bucket
    # dpf comes from the per-model stats delta — the process-wide
    # h2o3_score_dispatches_total source stays monotonic
    s0 = sess.stats.snapshot()

    def submit(fr):
        scoring.BATCHER.submit(model, fr)

    for _ in range(4):
        ths = [_threading.Thread(target=submit, args=(fr,))
               for fr in small]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
    s1 = sess.stats.snapshot()
    flushes = s1["batches"] - s0["batches"]
    disp = s1["dispatches"] - s0["dispatches"]
    dpf = disp / max(flushes, 1)
    if dpf > 2.0:
        # each small flush fits ONE row bucket: averaging > 2 dispatches
        # per flush means coalescing regressed to per-entry dispatch —
        # fail the stage loudly rather than record a stale claim
        raise RuntimeError(
            f"coalescing regression: {disp} fused dispatches over "
            f"{flushes} flushes ({dpf:.2f}/flush; expected ~1)")
    print(f"H2O3_BENCH score_dispatches_per_flush {dpf}", flush=True)
    print(f"H2O3_BENCH score_p99_ms {s1.get('p99_ms', 0.0)}", flush=True)
    return rows / dt, "score_rows_per_sec"


def run_rapids(n_rows: int = 2_000_000, reps: int = 5):
    """Rapids data-plane metric: chained-statement throughput through the
    statement fusion engine (rapids/fusion.py) vs the eager op-at-a-time
    evaluator — the SAME statements A/B'd with fusion forced off then on,
    warm in both modes (compiles excluded, the flagship convention). The
    fused number is the primary metric; the eager number and the ratio
    ride along so the trajectory shows the fusion win directly, and the
    data-plane counters prove the fused rows never left their shards."""
    import h2o3_tpu
    from h2o3_tpu.core import sharded_frame
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.rapids import fusion
    from h2o3_tpu.rapids.eval import Session, exec_rapids

    h2o3_tpu.init()
    rng = np.random.default_rng(4)
    fr = Frame(key="rapids_bench")
    a = rng.standard_normal(n_rows)
    a[rng.integers(0, n_rows, n_rows // 50)] = np.nan     # real NA traffic
    fr.add("a", Column.from_numpy(a))
    fr.add("b", Column.from_numpy(rng.standard_normal(n_rows)))
    fr.add("c", Column.from_numpy(rng.uniform(0.5, 2.0, n_rows)))
    fr.install()

    # a realistic munging batch: one long elementwise/ifelse chain, one
    # filter-mask statement, one reduction over a chain — ~20 prims that
    # the eager path runs as ~20 dispatches and the fused path as 3
    # representative feature-engineering chains: binning/flag/clip-style
    # cmp+ifelse+mask compositions (fully fusible — one program) plus an
    # arithmetic chain that exercises the FMA-boundary segments and a
    # fused reduction. Each eager prim is a full HBM read+write pass,
    # which is exactly the traffic statement fusion deletes.
    A, B, C = ("(cols rapids_bench [0])", "(cols rapids_bench [1])",
               "(cols rapids_bench [2])")
    clip = (f"(ifelse (> {A} 2) 2 (ifelse (< {A} -2) -2 {A}))")
    flags = (f"(& (| (> {B} 0.25) (< {C} 1)) "
             f"(& (== (is.na {A}) 0) (>= {B} -3)))")
    binned = (f"(ifelse (< {A} -1) 0 (ifelse (< {A} 0) 1 "
              f"(ifelse (< {A} 1) 2 (ifelse (< {A} 2) 3 4))))")
    stmts = [
        # one long fully-fusible chain (~25 prims, zero segment splits)
        f"(ifelse {flags} (+ {clip} {binned}) (- {binned} {clip}))",
        # arithmetic chain with mul->add FMA boundaries (segmented path)
        f"(- (+ (abs (- (* {A} 0.5) {C})) (* {B} 0.25)) (* {A} 0.125))",
        # fused chain feeding a reduction (one chain program + rollup)
        f"(sum (ifelse (> (+ {A} {B}) 0) (- {C} 0.5) (+ {C} 0.5)))",
    ]
    sess = Session("bench")

    def run_pass():
        for s in stmts:
            out = exec_rapids(s, sess)
            if hasattr(out, "col"):
                out.col(0).data.block_until_ready()

    def timed(on: bool) -> float:
        with fusion.force(on):
            run_pass()                       # warm (compiles excluded)
            t0 = time.perf_counter()
            for _ in range(reps):
                run_pass()
            return time.perf_counter() - t0

    rows_total = n_rows * len(stmts) * reps
    dt_eager = timed(False)
    sharded_frame.reset_counters()
    fusion.reset_counters()
    dt_fused = timed(True)
    dp = sharded_frame.counters()
    fc = fusion.counters()
    eager_rps = rows_total / dt_eager
    fused_rps = rows_total / dt_fused
    print(f"H2O3_BENCH rapids_eager_rows_per_sec {eager_rps}", flush=True)
    print(f"H2O3_BENCH rapids_fused_vs_eager {fused_rps / eager_rps}",
          flush=True)
    print(f"H2O3_BENCH rapids_fused_programs_compiled "
          f"{fc['fused_programs_compiled']}", flush=True)

    # ---- chained-session phase (ISSUE 14): the lazy whole-session DAG
    # (defer + CSE + dead-temp elimination + inlined intermediates, ONE
    # flush per pass) A/B'd against full op-at-a-time eager evaluation of
    # the same statement stream. The chain mirrors a real feature-
    # engineering session: a shared subexpression (CSE), an overwritten
    # temp (dead v1), and intermediates that only feed downstream temps
    # (inlined — never materialized).
    from h2o3_tpu.rapids import planner

    # the SAME heavy feature chains as the per-statement phase, split
    # across temps the way a client session actually builds them: eager
    # pays every prim dispatch plus a Column materialization per temp;
    # lazy flushes once, inlining the single-consumer intermediates into
    # one program, CSE-deduplicating the twin, and skipping the dead
    # overwritten temp entirely. A dedicated 2x frame keeps this phase
    # bandwidth-bound (the fixed per-flush planning cost amortized), the
    # regime a production munging session actually runs in.
    n_chain_rows = n_rows * 2
    cfr = Frame(key="rapids_chain")
    ca = rng.standard_normal(n_chain_rows)
    ca[rng.integers(0, n_chain_rows, n_chain_rows // 50)] = np.nan
    cfr.add("a", Column.from_numpy(ca))
    cfr.add("b", Column.from_numpy(rng.standard_normal(n_chain_rows)))
    cfr.add("c", Column.from_numpy(rng.uniform(0.5, 2.0, n_chain_rows)))
    cfr.install()
    CA, CB, CC = ("(cols rapids_chain [0])", "(cols rapids_chain [1])",
                  "(cols rapids_chain [2])")
    cclip = f"(ifelse (> {CA} 2) 2 (ifelse (< {CA} -2) -2 {CA}))"
    cflags = (f"(& (| (> {CB} 0.25) (< {CC} 1)) "
              f"(& (== (is.na {CA}) 0) (>= {CB} -3)))")
    cbinned = (f"(ifelse (< {CA} -1) 0 (ifelse (< {CA} 0) 1 "
               f"(ifelse (< {CA} 1) 2 (ifelse (< {CA} 2) 3 4))))")
    chain = [
        f"(tmp= rb_clip {cclip})",
        f"(tmp= rb_flags {cflags})",
        f"(tmp= rb_bin {cbinned})",
        f"(tmp= rb_bin2 {cbinned})",              # CSE twin (both live)
        "(tmp= rb_t (* rb_clip 2))",              # dead: overwritten next
        "(tmp= rb_t (+ rb_clip rb_bin))",
        "(tmp= rb_out (ifelse rb_flags rb_t (- rb_bin2 rb_clip)))",
        "(rm rb_clip)", "(rm rb_flags)", "(rm rb_t)",
    ]
    n_chain_stmts = sum(1 for s in chain if not s.startswith("(rm"))

    def chain_pass(csess):
        for s in chain:
            exec_rapids(s, csess)
        out = exec_rapids("rb_out", csess)
        out.col(0).data.block_until_ready()
        for k in ("rb_out", "rb_bin", "rb_bin2"):
            exec_rapids(f"(rm {k})", csess)

    csess = Session("bench_chain")

    def chain_once(lazy: bool) -> float:
        with planner.force(lazy), fusion.force(lazy):
            t0 = time.perf_counter()
            chain_pass(csess)
            return time.perf_counter() - t0

    chain_reps = reps + 3
    chain_rows = n_chain_rows * n_chain_stmts * chain_reps
    chain_once(False)                     # warm both modes (no compiles
    chain_once(True)                      # in the measured window)
    dt_chain_eager = 0.0
    dt_chain_lazy = 0.0
    for _ in range(chain_reps):           # interleaved A/B: machine noise
        dt_chain_eager += chain_once(False)   # hits both modes equally
        dt_chain_lazy += chain_once(True)
    csess.end()
    cfr.delete()
    chained_rps = chain_rows / dt_chain_lazy
    print(f"H2O3_BENCH rapids_chained_rows_per_sec {chained_rps}",
          flush=True)
    print(f"H2O3_BENCH rapids_chained_vs_eager "
          f"{dt_chain_eager / dt_chain_lazy}", flush=True)
    lz = planner.counters()
    print(f"H2O3_BENCH rapids_cse_hits {lz['cse_hits']}", flush=True)
    print(f"H2O3_BENCH rapids_dead_temps {lz['dead_temps_eliminated']}",
          flush=True)

    # ---- device sort metric (ISSUE 14): permutation computed, compacted
    # and applied on device — rows/sec through sort_frame, warm.
    from h2o3_tpu.ops.sort import sort_frame

    sort_reps = max(reps // 2, 2)
    sort_frame(fr, ["a"]).col(0).data.block_until_ready()   # warm compile
    t0 = time.perf_counter()
    for _ in range(sort_reps):
        sort_frame(fr, ["a"]).col(0).data.block_until_ready()
    dt_sort = time.perf_counter() - t0
    sort_rps = n_rows * sort_reps / dt_sort
    print(f"H2O3_BENCH rapids_sort_rows_per_sec {sort_rps}", flush=True)
    print(f"H2O3_BENCH rapids_gathered_rows "
          f"{sharded_frame.counters()['gathered_rows']}", flush=True)
    sess.end()
    fr.delete()
    return fused_rps, "rapids_fused_rows_per_sec"


def run_recover():
    """Recovery drill metric: wallclock seconds from coordinator-kill to
    the cloud re-entering HEALTHY, with the autonomous watchdog doing the
    election and the simulated ex-coordinator's rejoin being the only
    external event. Control-plane only (memory KV), so it runs on CPU and
    measures the watchdog/supervisor machinery, not device compiles."""
    import json
    import os
    import tempfile
    import time as _time

    # isolated checkpoint dir: the live watchdog must never see (let alone
    # strike-GC) a production cloud's real durable job-progress records on
    # this host — memory_kv isolates the KV but not files
    os.environ["H2O_TPU_OPLOG_CKPT_DIR"] = tempfile.mkdtemp(
        prefix="h2o3_bench_recover_")
    os.environ["H2O_TPU_ELECTION_GRACE_S"] = "0.2"
    os.environ["H2O_TPU_HEARTBEAT_STALE_S"] = "1.0"
    os.environ["H2O_TPU_AUTO_RECOVER"] = "1"
    os.environ["H2O_TPU_OPLOG_CHECKPOINT_OPS"] = "0"
    from h2o3_tpu.core import failure
    from h2o3_tpu.parallel import distributed as D
    from h2o3_tpu.parallel import oplog, supervisor, watchdog

    with D.memory_kv() as kv:
        D.process_count = lambda: 2          # bench subprocess: safe to pin
        D.write_epoch_record(0, 1)           # process 1 leads ...
        D.set_leader(1, 0)                   # ... and just died
        kv["h2o3/heartbeat/1"] = json.dumps({"ts": _time.time() - 999,
                                             "proc": 1})
        failure.heartbeat()
        oplog.reset()
        supervisor.reset()
        watchdog.reset()
        t0 = time.perf_counter()
        wd = watchdog.Watchdog(interval=0.05, follow=False).start()
        try:
            deadline = _time.time() + 30
            while not D.is_coordinator() and _time.time() < deadline:
                _time.sleep(0.01)
            # the restarted ex-coordinator rejoins: fresh beat + record
            kv["h2o3/heartbeat/1"] = json.dumps({"ts": _time.time(),
                                                 "proc": 1, "inc": 1})
            # HEALTHY must come from a fresh evidence fold (not the
            # election's reset): poll evaluate() itself
            while _time.time() < deadline:
                if D.is_coordinator() and \
                        supervisor.evaluate() == supervisor.HEALTHY:
                    break
                _time.sleep(0.01)
            dt = time.perf_counter() - t0
            ok = D.is_coordinator() and \
                supervisor.state() == supervisor.HEALTHY
        finally:
            wd.stop()
            oplog.reset()
            supervisor.reset()
            D.reset_leadership()
    if not ok:
        raise RuntimeError("recovery drill did not reach HEALTHY")
    return dt, "recover_secs_to_healthy"


def run_search_recover(n_rows: int = 1_500):
    """Search-recovery drill metric: wallclock seconds from a simulated
    coordinator loss mid-grid (two members already durably done, the rest
    orphaned) to the watchdog re-dispatching the search from its durable
    state and the leaderboard completing — zero manual recovery calls.
    Members run two-wide (collective-free GLM combos), so the aux
    ``search_members_overlap`` line is the concurrency evidence."""
    import json as _json
    import tempfile
    import time as _time

    import numpy as np

    # isolated checkpoint dir: never touch a production cloud's records
    os.environ["H2O_TPU_OPLOG_CKPT_DIR"] = tempfile.mkdtemp(
        prefix="h2o3_bench_search_recover_")
    os.environ["H2O_TPU_AUTO_RECOVER"] = "1"
    os.environ["H2O_TPU_SEARCH_CONCURRENCY"] = "2"
    from h2o3_tpu.automl import search as _search
    from h2o3_tpu.core.dkv import DKV
    from h2o3_tpu.core.frame import Column, Frame, T_CAT
    from h2o3_tpu.core.job import Job
    from h2o3_tpu.grid import H2OGridSearch
    from h2o3_tpu.models.model_builder import BUILDERS
    from h2o3_tpu.parallel import distributed as D
    from h2o3_tpu.parallel import oplog, supervisor, watchdog

    rng = np.random.default_rng(0)
    X = rng.normal(size=(n_rows, 3))
    yv = np.where(X[:, 0] + 0.5 * X[:, 1] +
                  rng.normal(scale=0.3, size=n_rows) > 0, "Y", "N")
    with D.memory_kv():
        oplog.reset()
        supervisor.reset()
        watchdog.reset()
        _search.reset_stats()
        fr = Frame.from_numpy(X, names=["a", "b", "c"])
        fr.add("y", Column.from_numpy(yv, ctype=T_CAT))
        fr.install()     # the resume path looks the frame up by key
        grid_id = "bench_search_recover_grid"
        job = Job(description="glm Grid Build", dest=grid_id)
        base = BUILDERS["glm"](family="binomial")
        grid = H2OGridSearch(base, {"alpha": [0.0, 0.3, 0.6, 1.0]},
                             grid_id=grid_id)
        grid._search_job = job

        # kill the search after two members settle: further dispatches die
        # the way a lost coordinator's would (engine-level crash, durable
        # state already holding the finished members)
        settled = {"n": 0}
        orig = _search.SearchEngine._build_one

        def dying(self, m, build_fn, score_fn=None):
            if settled["n"] >= 2:
                raise RuntimeError("simulated coordinator loss")
            settled["n"] += 1
            return orig(self, m, build_fn, score_fn)

        _search.SearchEngine._build_one = dying
        try:
            grid.train(y="y", training_frame=fr)
        except Exception:   # noqa: BLE001 — the simulated loss, by design
            pass
        finally:
            _search.SearchEngine._build_one = orig
        # the coordinator is gone: its Job object dies with the process —
        # only the durable search state survives, and the watchdog must
        # rebuild the Job shell under the ORIGINAL key
        DKV.remove(str(job.key))

        t0 = time.perf_counter()
        wd = watchdog.Watchdog(interval=0.05, follow=False).start()
        try:
            deadline = _time.time() + 60
            resumed_job = None
            while _time.time() < deadline:
                resumed_job = DKV.get(str(job.key))
                if isinstance(resumed_job, Job) and \
                        resumed_job.status == Job.DONE:
                    break
                _time.sleep(0.02)
            dt = time.perf_counter() - t0
            ok = isinstance(resumed_job, Job) and \
                resumed_job.status == Job.DONE
        finally:
            wd.stop()
            oplog.reset()
            supervisor.reset()
            watchdog.reset()
    stats = _search.stats()
    if not ok:
        raise RuntimeError(
            f"search-recovery drill did not complete: {_json.dumps(stats)}")
    if stats.get("searches_resumed", 0) < 1 or \
            stats.get("members_done", 0) < 4:
        raise RuntimeError(
            f"search resumed without finishing its members: "
            f"{_json.dumps(stats)}")
    print(f"H2O3_BENCH search_members_overlap {stats.get('overlap', 0)}",
          flush=True)
    return dt, "search_recover_secs"


def run_artifact(train_rows: int = 20_000, ntrees: int = 10,
                 batch_rows: int = 256, sustain_s: float = 3.0):
    """Serving-tier artifact metrics (ROADMAP item 3 'Done' criterion):

    - ``artifact_cold_start_secs`` — wallclock of a fresh standalone
      runner's load to its first prediction (manifest + executable load +
      one batch), timed in THIS process: one process holds the chip, so a
      child that needs it cannot be started from here. Printed as an
      auxiliary H2O3_BENCH line.
    - ``artifact_qps`` — sustained request rate through the standalone
      runner at `batch_rows` rows/request (returned as the stage metric).
    """
    import os
    import tempfile

    import h2o3_tpu
    from h2o3_tpu import artifact
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.models.tree.gbm import GBM

    h2o3_tpu.init()
    rng = np.random.default_rng(5)

    def make(n, with_y):
        fr = Frame()
        logit = np.zeros(n)
        for i in range(6):
            x = rng.standard_normal(n)
            logit += x * ((-1) ** i) * 0.5
            fr.add(f"n{i}", Column.from_numpy(x))
        codes = rng.integers(0, 4, n)
        fr.add("c0", Column.from_numpy(
            np.array(["a", "b", "c", "d"])[codes], ctype="enum"))
        if with_y:
            yy = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)), "Y", "N")
            fr.add("y", Column.from_numpy(yy, ctype="enum"))
        return fr

    model = GBM(ntrees=ntrees, max_depth=5, seed=6).train(
        y="y", training_frame=make(train_rows, True))
    art_dir = tempfile.mkdtemp(prefix="h2o3_bench_artifact_")
    artifact.export_model(model, art_dir, buckets=[batch_rows])

    # one CSV batch for the runner
    csv_path = os.path.join(art_dir, "bench_batch.csv")
    fr = make(batch_rows, False)
    cols = [(nm, np.asarray(fr.col(nm).data)[:batch_rows]
             if not fr.col(nm).is_categorical else
             np.asarray(fr.col(nm).domain, object)[
                 np.asarray(fr.col(nm).data)[:batch_rows]])
            for nm in fr.names]
    with open(csv_path, "w") as f:
        f.write(",".join(nm for nm, _ in cols) + "\n")
        for i in range(batch_rows):
            f.write(",".join(str(c[i]) for _, c in cols) + "\n")

    from h2o3_genmodel.aot import load_artifact
    from h2o3_genmodel.predict_csv import read_csv_columns

    t0 = time.perf_counter()
    s = load_artifact(art_dir)
    s.score(read_csv_columns(csv_path))
    cold = time.perf_counter() - t0
    print(f"H2O3_BENCH artifact_cold_start_secs {cold}", flush=True)

    s = load_artifact(art_dir)
    cols_d = read_csv_columns(csv_path)
    X = s.pack_features(cols_d)
    s.raw_predict(X)                      # warm (matches flagship convention)
    t0 = time.perf_counter()
    reqs = 0
    while time.perf_counter() - t0 < sustain_s:
        s.raw_predict(X)
        reqs += 1
    dt = time.perf_counter() - t0
    return reqs / dt, "artifact_qps"


def run_parse(n_rows: int = 400_000, n_num: int = 6, n_cat: int = 2):
    """Ingest metric (ISSUE 15): chunked sharded parse throughput in
    MB/sec over one large mixed CSV, A/B'd against the monolithic
    single-thread path on the SAME file (aux ``parse_chunked_vs_mono``,
    acceptance bar >= 1.5x). ``parse_coordinator_ingest_bytes`` rides
    along and must read 0 for the chunked run — the zero-gather contract
    the counter exists for — plus the chunk count and the split/parse/ship
    overlap ratio."""
    import os
    import tempfile

    import h2o3_tpu
    from h2o3_tpu.ingest import chunked
    from h2o3_tpu.ingest.parser import import_file

    h2o3_tpu.init()
    rng = np.random.default_rng(7)
    d = tempfile.mkdtemp(prefix="h2o3_bench_parse_")
    path = os.path.join(d, "bench_parse.csv")
    import pandas as pd

    cols = {}
    for i in range(n_num):
        cols[f"n{i}"] = np.round(rng.standard_normal(n_rows), 6)
    doms = [np.array(["alpha", "beta", "gamma", "delta"]),
            np.array(["x", "y", "z"])]
    for i in range(n_cat):
        cols[f"c{i}"] = doms[i % 2][rng.integers(0, len(doms[i % 2]),
                                                 n_rows)]
    pd.DataFrame(cols).to_csv(path, index=False)
    size_mb = os.path.getsize(path) / 1e6

    def timed(chunked_on: bool, tag: str) -> float:
        os.environ["H2O_TPU_INGEST_CHUNKED"] = "1" if chunked_on else "0"
        try:
            t0 = time.perf_counter()
            fr = import_file(path, destination_frame=f"bench_parse_{tag}")
            fr.col(fr.names[0]).data.block_until_ready()
            dt = time.perf_counter() - t0
            fr.delete()
            return dt
        finally:
            os.environ.pop("H2O_TPU_INGEST_CHUNKED", None)

    # tiny warm parse per mode keeps import/installation cost out of the
    # measured window (the flagship warm-up convention)
    warm = os.path.join(d, "warm.csv")
    with open(warm, "w") as f:
        f.write("a,b\n1,x\n2,y\n")
    for on in (False, True):
        os.environ["H2O_TPU_INGEST_CHUNKED"] = "1" if on else "0"
        import_file(warm, destination_frame="bench_parse_warm").delete()
    os.environ.pop("H2O_TPU_INGEST_CHUNKED", None)

    dt_mono = timed(False, "mono")
    c0 = chunked.counters()
    dt_chunked = timed(True, "chunked")
    c1 = chunked.counters()
    coord_delta = (c1["coordinator_ingest_bytes"]
                   - c0["coordinator_ingest_bytes"])
    print(f"H2O3_BENCH parse_mono_mb_per_sec {size_mb / dt_mono}",
          flush=True)
    print(f"H2O3_BENCH parse_chunked_vs_mono {dt_mono / dt_chunked}",
          flush=True)
    print(f"H2O3_BENCH parse_coordinator_ingest_bytes {coord_delta}",
          flush=True)
    print(f"H2O3_BENCH parse_chunks {c1['chunks'] - c0['chunks']}",
          flush=True)
    print(f"H2O3_BENCH parse_overlap_ratio {c1['overlap_ratio']}",
          flush=True)
    return size_mb / dt_chunked, "parse_mb_per_sec"


def run_glm(n_rows: int = 1_000_000, p: int = 32, iters: int = 20):
    """GLM IRLS secondary metric (matches the repo-root bench_glm shape)."""
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((n_rows, p)), jnp.float32)
    true_b = jnp.asarray(rng.standard_normal(p), jnp.float32)
    y = (jax.nn.sigmoid(X @ true_b) > 0.5).astype(jnp.float32)

    @jax.jit
    def irls_step(beta, _):
        eta = X @ beta[:-1] + beta[-1]
        mu = jax.nn.sigmoid(eta)
        w = jnp.maximum(mu * (1 - mu), 1e-6)
        z = eta + (y - mu) / w
        Xa = jnp.concatenate([X, jnp.ones((X.shape[0], 1), X.dtype)], axis=1)
        gram = (Xa * w[:, None]).T @ Xa + 1e-6 * jnp.eye(p + 1, dtype=X.dtype)
        rhs = Xa.T @ (w * z)
        return jnp.linalg.solve(gram, rhs), 0.0

    @jax.jit
    def run(beta):
        beta, _ = lax.scan(irls_step, beta, None, length=iters)
        return beta

    beta0 = jnp.zeros(p + 1, jnp.float32)
    run(beta0).block_until_ready()
    t0 = time.perf_counter()
    run(beta0).block_until_ready()
    dt = time.perf_counter() - t0
    return n_rows * iters / dt, "glm_irls_rows_per_sec"


def run_pipeline(train_rows: int = 20_000, n_rows: int = 200_000,
                 reps: int = 5, ntrees: int = 10, max_depth: int = 5):
    """Munge→score pipeline-fusion metric (ISSUE 16): raw columns through
    a lazy Rapids feature chain into a GBM predict, A/B'd with the splice
    forced off (staged: flush the munge DAG, materialize the engineered
    Columns, then bucketed scoring) vs on (ONE fused program per row
    bucket, zero intermediate Columns). Each repetition re-engineers the
    features from the raw frame — a staged predict flushes the DAG, so
    every pass must pay (or fuse away) the full munge cost, exactly like
    a serving tier scoring raw rows. Warm pass excluded in both modes;
    the pipeline counters prove the fused passes materialized nothing."""
    import h2o3_tpu
    from h2o3_tpu import pipeline, scoring
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.models.tree.gbm import GBM
    from h2o3_tpu.rapids import fusion, planner
    from h2o3_tpu.rapids.eval import Session, exec_rapids

    h2o3_tpu.init()
    rng = np.random.default_rng(7)

    # train on the ENGINEERED feature names — serving receives raw r1/r2
    tr = Frame()
    x1 = rng.standard_normal(train_rows)
    x2 = rng.standard_normal(train_rows)
    logit = 0.8 * x1 - 0.6 * x2
    tr.add("x1", Column.from_numpy(x1))
    tr.add("x2", Column.from_numpy(x2))
    tr.add("y", Column.from_numpy(
        np.where(rng.random(train_rows) < 1 / (1 + np.exp(-logit)),
                 "Y", "N"), ctype="enum"))
    model = GBM(ntrees=ntrees, max_depth=max_depth, seed=7).train(
        y="y", training_frame=tr)
    ssn = scoring.session_for(model)

    raw = Frame(key="pipe_bench_raw")
    r1 = rng.standard_normal(n_rows)
    r1[::97] = np.nan                       # real NA traffic
    raw.add("r1", Column.from_numpy(r1))
    raw.add("r2", Column.from_numpy(rng.standard_normal(n_rows)))
    raw.install()

    sess = Session("bench_pipe")
    seq = [0]
    R1, R2 = "(cols pipe_bench_raw [0])", "(cols pipe_bench_raw [1])"

    def engineer():
        # fresh temps every pass: the staged mode flushed the previous
        # DAG, so reusing a frame would let it skip the munge entirely
        seq[0] += 1
        p = f"pb{seq[0]}"
        exec_rapids(f"(tmp= {p}_a (+ {R1} 0.5))", sess)
        exec_rapids(f"(tmp= {p}_b (ifelse (> {R2} 0) {R2} {p}_a))", sess)
        return exec_rapids(
            f'(tmp= {p}_pf (colnames= (cbind {p}_a {p}_b) [0 1] '
            f'["x1" "x2"]))', sess)

    def timed(on: bool) -> float:
        with planner.force(True), fusion.force(True), pipeline.force(on):
            ssn.predict(engineer())          # warm (compiles excluded)
            t0 = time.perf_counter()
            for _ in range(reps):
                out = ssn.predict(engineer())
                c = out.col(0)
                if hasattr(c.data, "block_until_ready"):
                    c.data.block_until_ready()
            return time.perf_counter() - t0

    dt_staged = timed(False)
    pipeline.reset_counters()
    dt_fused = timed(True)
    pc = pipeline.counters()
    staged_rps = n_rows * reps / dt_staged
    fused_rps = n_rows * reps / dt_fused
    print(f"H2O3_BENCH pipeline_staged_rows_per_sec {staged_rps}",
          flush=True)
    print(f"H2O3_BENCH pipeline_vs_staged {fused_rps / staged_rps}",
          flush=True)
    # zero-materialization evidence next to the throughput number: the
    # fused passes spliced the munge DAG straight into the score program
    # (same counters as the /3/ScoringMetrics pipeline block)
    print(f"H2O3_BENCH pipeline_fused_dispatches "
          f"{pc['fused_dispatches']}", flush=True)
    print(f"H2O3_BENCH pipeline_materialized_columns "
          f"{pc['materialized_columns']}", flush=True)
    if pc["materialized_columns"]:
        # the whole point of the splice is zero intermediate Columns —
        # fail the stage loudly rather than record a stale claim
        raise RuntimeError(
            f"pipeline fusion regression: {pc['materialized_columns']} "
            "intermediate columns materialized during fused passes "
            "(expected 0)")
    sess.end()
    return fused_rps, "pipeline_rows_per_sec"


def run_oom_degrade(train_rows: int = 20_000, score_rows: int = 60_000):
    """Memory-safety metric (ISSUE 20): wall seconds for a scoring pass
    that hits device OOM (injected ``mem.exhausted``, twice) and
    completes through the degradation ladder — sweep, halve, bounded
    backoff — instead of failing. The ``bigger_than_hbm_ok`` aux line is
    the bigger-than-budget acceptance check: with
    ``H2O_TPU_MEM_BUDGET_MB`` pinned far below the frame's working set,
    train input binning and scoring stream row-chunk windows and the
    predictions must match the unbudgeted single-dispatch run bitwise."""
    import os

    import h2o3_tpu
    from h2o3_tpu import scoring
    from h2o3_tpu.core import failure
    from h2o3_tpu.core.frame import Column, Frame
    from h2o3_tpu.memory import budget, stream
    from h2o3_tpu.models.tree.gbm import GBM

    h2o3_tpu.init()
    rng = np.random.default_rng(11)

    def make(n, with_y):
        fr = Frame()
        logit = np.zeros(n)
        for i in range(6):
            x = rng.standard_normal(n)
            if i == 0:
                x[rng.integers(0, n, n // 50)] = np.nan   # real NA traffic
            logit += np.nan_to_num(x) * ((-1) ** i) * 0.5
            fr.add(f"n{i}", Column.from_numpy(x))
        if with_y:
            yy = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)),
                          "Y", "N")
            fr.add("y", Column.from_numpy(yy, ctype="enum"))
        return fr

    model = GBM(ntrees=5, max_depth=4, seed=7).train(
        y="y", training_frame=make(train_rows, True))
    sess = scoring.session_for(model)
    score_fr = make(score_rows, False)

    def preds(fr):
        out = sess.predict(fr)
        return [np.asarray(out.col(i).data)[:fr.nrows]
                for i in range(len(out.names))]

    baseline = preds(score_fr)            # unbudgeted single dispatch

    saved = os.environ.get("H2O_TPU_MEM_BUDGET_MB")
    os.environ["H2O_TPU_MEM_BUDGET_MB"] = \
        os.environ.get("H2O3_BENCH_MEM_BUDGET_MB", "2")
    try:
        stream.reset_counters()
        chunked = preds(score_fr)
        sc = stream.counters()
        bitwise = all(np.array_equal(a, b, equal_nan=True)
                      for a, b in zip(baseline, chunked))
        ok = int(bitwise and sc["chunked_runs"] > 0
                 and sc["windows"] > 1)
        print(f"H2O3_BENCH bigger_than_hbm_ok {ok}", flush=True)
        print(f"H2O3_BENCH mem_windows {sc['windows']}", flush=True)
        if not bitwise:
            raise RuntimeError(
                "memory-safety regression: chunk-streamed predictions "
                "diverged from the single-dispatch baseline")
        # the ladder: two injected OOMs inside the stream driver — the
        # bounded retry budget (3 attempts) absorbs both and the pass
        # completes; the primary metric is how long recovery costs
        stream.reset_counters()
        t0 = time.perf_counter()
        with failure.inject("mem.exhausted", times=2):
            recovered = preds(score_fr)
        dt = time.perf_counter() - t0
        sc = stream.counters()
        if not all(np.array_equal(a, b, equal_nan=True)
                   for a, b in zip(baseline, recovered)):
            raise RuntimeError(
                "memory-safety regression: ladder-recovered predictions "
                "diverged from the baseline")
        if sc["ladder_recoveries"] < 1:
            raise RuntimeError(
                "memory-safety regression: injected OOM never walked "
                "the degradation ladder")
        print(f"H2O3_BENCH mem_ladder_halvings {sc['ladder_halvings']}",
              flush=True)
    finally:
        if saved is None:
            os.environ.pop("H2O_TPU_MEM_BUDGET_MB", None)
        else:
            os.environ["H2O_TPU_MEM_BUDGET_MB"] = saved
        budget.reset_pressure()
    return dt, "mem_degrade_recover_secs"


if __name__ == "__main__":
    # one stage per process: H2O3_BENCH_ONLY=<stage> python -m h2o3_tpu.bench
    # (unset = the flagship GBM stage)
    import os

    arm_stage_autopsy()      # dying stages leave a flight record to read
    mode = os.environ.get("H2O3_BENCH_ONLY", "")
    if mode == "profile":
        # one profile artifact per round (VERDICT r4 item 3): an XLA trace
        # of a short flagship run, viewable with tensorboard/xprof
        from h2o3_tpu.utils import timeline

        pdir = os.environ.get("H2O3_PROFILE_DIR", "profile_out")
        with timeline.trace(pdir):
            value, metric = run_flagship(n_rows=200_000, ntrees=5)
        metric = "gbm_profiled_rows_per_sec"
        print(f"profile written to {pdir}", flush=True)
    elif mode == "drf":
        value, metric = run_drf_deep()
    elif mode == "compile":
        value, metric = run_compile_probe()
    elif mode == "glm":
        value, metric = run_glm()
    elif mode == "recover":
        value, metric = run_recover()
    elif mode == "search-recover":
        value, metric = run_search_recover()
    elif mode == "artifact":
        value, metric = run_artifact(
            train_rows=int(os.environ.get("H2O3_BENCH_ARTIFACT_TRAIN_ROWS",
                                          20_000)))
    elif mode == "score":
        value, metric = run_scoring(
            train_rows=int(os.environ.get("H2O3_BENCH_SCORE_TRAIN_ROWS",
                                          20_000)))
    elif mode == "rapids":
        value, metric = run_rapids(
            n_rows=int(os.environ.get("H2O3_BENCH_RAPIDS_ROWS", 2_000_000)))
    elif mode == "pipeline":
        value, metric = run_pipeline(
            train_rows=int(os.environ.get("H2O3_BENCH_PIPELINE_TRAIN_ROWS",
                                          20_000)),
            n_rows=int(os.environ.get("H2O3_BENCH_PIPELINE_ROWS", 200_000)))
    elif mode == "parse":
        value, metric = run_parse(
            n_rows=int(os.environ.get("H2O3_BENCH_PARSE_ROWS", 400_000)))
    elif mode == "oom-degrade":
        value, metric = run_oom_degrade(
            score_rows=int(os.environ.get("H2O3_BENCH_OOM_ROWS", 60_000)))
    elif mode == "pallas":
        # Pallas-vs-XLA on silicon: same flagship config, Pallas histogram
        # path forced on (smaller tree count to fit the stage budget)
        os.environ["H2O_TPU_PALLAS_HIST"] = "1"
        value, metric = run_flagship(
            n_rows=int(os.environ.get("H2O3_BENCH_ROWS", 1_000_000)),
            ntrees=10)
        metric = "gbm_pallas_rows_per_sec"
    else:
        value, metric = run_flagship(
            n_rows=int(os.environ.get("H2O3_BENCH_ROWS", 1_000_000)),
            ntrees=int(os.environ.get("H2O3_BENCH_TREES", 20)))
    # the lifecycle phase report rides along as aux lines (the ISSUE-12
    # acceptance evidence: backend_init .. first_compile durations next
    # to the stage's primary metric, mirrored on GET /3/Runtime)
    try:
        from h2o3_tpu.obs import phases as _phases

        for _name, _ms in _phases.phase_report().items():
            print(f"H2O3_BENCH phase_{_name}_ms {_ms}", flush=True)
    except Exception:   # noqa: BLE001 — reporting must not fail a stage
        pass
    print(f"H2O3_BENCH {metric} {value}", flush=True)

"""Checked-in analysis registry: the audited inputs every pass starts from.

This file is the reviewable surface of the analyzer. It declares:

- the **mirrored roots** — functions every process of a multi-process
  cloud executes in lockstep (oplog op handlers, broadcast trainer
  entries); the mirrored-program pass closes over the project call graph
  from here;
- the **knob helpers** — the sanctioned ``os.environ`` accessors (reads
  anywhere else inside mirrored code are findings);
- the **guarded functions** — audited call sites that LOOK divergent but
  are provably mirrored-safe; every entry carries its one-line audit
  reason. Adding an entry here is a review event, exactly like editing a
  lock-order declaration;
- the **host-side modules** — control-plane/observability code that never
  lowers or dispatches device programs: mirrored findings inside them are
  suppressed (the call graph still flows THROUGH them);
- the lock-order scope + declared order, the serialization allowlist, the
  compat-routing API list, and the sync-hygiene configuration.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# mirrored-program pass
# ---------------------------------------------------------------------------

# every process replaying the oplog walks these in lockstep; any
# per-process-divergent decision reachable from here desynchronizes the
# device program sequence around collectives (the PR-5/PR-7 invariant)
MIRRORED_ROOTS = (
    "h2o3_tpu.parallel.oplog._apply",                 # all oplog op handlers
    "h2o3_tpu.models.model_builder.ModelBuilder.train",   # broadcast trains
    "h2o3_tpu.scoring.execute_batch",                 # score_batch replays
    "h2o3_tpu.rapids.eval.exec_rapids",               # rapids op replays
    "h2o3_tpu.automl.search.SearchEngine.run",        # search member walks
)

# sanctioned env accessors: defaulting + documentation ride these, and the
# ops contract pins the env uniform across cloud processes. A RAW environ
# read inside mirrored code bypasses that contract.
KNOB_HELPERS = frozenset({
    "h2o3_tpu.parallel.retry.env_int",
    "h2o3_tpu.parallel.retry.env_float",
    "h2o3_tpu.scoring._env_buckets",
    "h2o3_tpu.parallel.ckpt.job_ckpt_iters",
    "h2o3_tpu.core.runtime.OptArgs.from_env",      # boot-time config fold
    "h2o3_tpu.core.runtime.place_jax_cache",       # JAX cache DIR (host I/O)
    "h2o3_tpu.core.sharded_frame.enabled",         # H2O_TPU_SHARDED_PLANE
    "h2o3_tpu.rapids.fusion.enabled",              # H2O_TPU_RAPIDS_FUSION
    "h2o3_tpu.rapids.planner.enabled",             # H2O_TPU_RAPIDS_LAZY —
    # reads process_count() too: deferral is deterministically OFF on
    # multi-process clouds (a coordinator-only flush must never dispatch
    # unmirrored collectives), so every process takes the same branch
    "h2o3_tpu.scoring.enabled",                    # H2O_TPU_SCORE_FAST —
    # the fused leaf routing (leaf_assignment/staged_proba replay) reads
    # it mirrored; like the sharded-plane switch, the documented contract
    # is "set identically on every process" (README env index)
    "h2o3_tpu.pipeline.enabled",                   # H2O_TPU_PIPELINE_FUSION
    # — requires planner.enabled() which is deterministically OFF on
    # multi-process clouds, so the splice never fires mirrored
    "h2o3_tpu.artifact.compile_cache.cache_dir",   # cache DIR (host I/O)
    # chunked sharded ingest knobs (ISSUE 15): read mirrored inside the
    # import_file / parse_stream op replays; the ops contract pins the
    # env uniform, and chunk layout is a pure function of (bytes, knobs)
    "h2o3_tpu.ingest.chunked.enabled",             # H2O_TPU_INGEST_CHUNKED
    "h2o3_tpu.ingest.chunked.chunk_bytes",         # H2O_TPU_INGEST_CHUNK_BYTES
    "h2o3_tpu.ingest.chunked.ingest_workers",      # H2O_TPU_INGEST_WORKERS
    "h2o3_tpu.ingest.chunked.parquet_batch",       # lazy-parquet batch width
    "h2o3_tpu.automl.search.search_concurrency",
    # — H2O_TPU_SEARCH_CONCURRENCY: deterministically 1 when oplog is
    # active (every process walks the identical member sequence); the
    # env/admission sizing only runs single-process
    "h2o3_tpu.automl.search.search_ckpt_enabled",
    # — H2O_TPU_SEARCH_CKPT gates host-side durable-state writes only;
    # it never shapes the member/program sequence
    "h2o3_tpu.automl.search.member_deadline_s",
    # — H2O_TPU_SEARCH_MEMBER_DEADLINE_S is deterministically 0 when
    # oplog is active (per-process deadline kills would desynchronize the
    # mirrored member walks)
    # HBM budget planner knobs (ISSUE 20): read mirrored inside fused
    # dispatch; the ops contract pins the env uniform, and the window
    # plan is a pure function of (env, rows, estimates) so every process
    # streams the same windows — and a chunked window computes bitwise
    # the same rows as a full dispatch by the row-local contract
    "h2o3_tpu.memory.budget.budget_mb",       # H2O_TPU_MEM_BUDGET_MB
    "h2o3_tpu.memory.budget.headroom",        # H2O_TPU_MEM_HEADROOM
    "h2o3_tpu.memory.budget.pressure_cooldown_s",
    # — H2O_TPU_MEM_PRESSURE_COOLDOWN_S gates host-side admission
    # shedding only; it never shapes a device program
})

# audited divergent-looking call sites that are mirrored-safe; reason is
# the audit note (shown next to any suppressed finding with --verbose)
GUARDED = {
    "h2o3_tpu.models.model_builder.random_seed":
        "the ONE seed-derivation policy: REST pins wildcard seeds before "
        "any broadcast (_pin_seed_and_wire), so this fresh entropy only "
        "runs library-mode (single process)",
    "h2o3_tpu.core.dkv.Key.make":
        "random key suffixes are process-local DKV names; cross-process "
        "keys always ride op payloads, never shape device programs",
    "h2o3_tpu.models.model_builder.ModelBuilder._out_of_time":
        "wall-clock budget gate: broadcast handlers clear "
        "max_runtime_secs before the op ships (train/grid/automl), so "
        "_deadline is None whenever this runs mirrored",
    "h2o3_tpu.models.model_builder.ModelBuilder.train":
        "t0/run_time_ms wall-clock reads are model metadata only; the "
        "deadline they seed is cleared for broadcast ops (see "
        "_out_of_time)",
    "h2o3_tpu.grid.H2OGridSearch.train":
        "wall-clock budget loop: the REST grid handler zeroes "
        "search_criteria max_runtime_secs before broadcast, so the "
        "time-based break never fires mirrored",
    "h2o3_tpu.automl.automl.H2OAutoML.__init__":
        "the timestamp default for project_name only fires when the "
        "caller passed none; broadcast specs always pin project_name "
        "(the coordinator's value rides the op payload)",
    "h2o3_tpu.automl.automl.H2OAutoML.train":
        "wall-clock budget + explore window: the REST AutoML handler "
        "zeroes max_runtime_secs before broadcast (recorded in the op "
        "spec), so budget branches never fire mirrored",
}

# control-plane / observability modules: they never lower or dispatch a
# device program, so per-process wall-clock / env decisions inside them
# cannot desynchronize collectives. Reachability still flows through.
HOST_SIDE_MODULES = {
    "h2o3_tpu/obs/": "observability plane: span ids/timestamps are "
                     "process-local by design",
    "h2o3_tpu/utils/": "logging/timeline/2D-table host utilities",
    "h2o3_tpu/api/": "REST layer runs on the coordinator only; broadcast "
                     "payload prep is covered by its own fixtures",
    "h2o3_tpu/parallel/retry.py": "backoff timing is per-process host "
                                  "waiting, not program lowering",
    "h2o3_tpu/parallel/supervisor.py": "health state machine (host)",
    "h2o3_tpu/parallel/watchdog.py": "recovery daemon (host)",
    "h2o3_tpu/parallel/distributed.py": "KV transport + leadership",
    "h2o3_tpu/parallel/ckpt.py": "durable-progress I/O timing is "
                                 "host-side; restored STATE is shared "
                                 "via one file by contract",
    "h2o3_tpu/parallel/oplog.py": "turnstile/ack deadlines are "
                                  "coordinator-host waiting; the replay "
                                  "handlers' CALLEES are the mirrored "
                                  "surface",
    "h2o3_tpu/admission.py": "serving admission happens before the op is "
                             "published; all processes see the op or "
                             "none do",
    "h2o3_tpu/core/failure.py": "heartbeat/health evidence is host-side "
                                "supervision input",
    "h2o3_tpu/core/job.py": "job lifecycle metadata (timestamps/status); "
                            "the device work lives in the builders",
    "h2o3_tpu/persist/": "storage backends (host I/O)",
}

# ---------------------------------------------------------------------------
# lock-order pass
# ---------------------------------------------------------------------------

# modules whose lock acquisitions are modeled (ISSUE scope: the cloud
# control plane + the serving session)
LOCK_SCOPE = (
    "h2o3_tpu/parallel/",
    "h2o3_tpu/core/job.py",
    "h2o3_tpu/scoring.py",
)

# declared acquisition order: (outer, inner) pairs that are LEGAL; the
# observed reverse edge is a finding even without a full cycle. Lock ids
# are "<module-tail>.<name>" / "<module-tail>.<Class>.<attr>".
LOCK_ORDER = (
    # supervisor state machine may fail jobs (job.fail takes the status
    # lock) — job code must never call back into supervisor state
    ("supervisor._LOCK", "job.Job._status_lock"),
)

# ---------------------------------------------------------------------------
# serialization pass
# ---------------------------------------------------------------------------

# the sanctioned homes of restricted-Unpickler SUBCLASSES (a security
# surface that must not proliferate). NOTE: nothing is exempt from the
# raw pickle.load / allow_pickle=True ban — this list only bounds where
# Unpickler definitions may live; raw loads are findings everywhere.
PICKLE_ALLOWED = (
    "h2o3_tpu/utils/unpickle.py",
    "h2o3_tpu/parallel/ckpt.py",
    "h2o3_tpu/artifact/",
    "h2o3_tpu/api/routes_ext.py",
    "h2o3_genmodel/aot.py",
)

# ---------------------------------------------------------------------------
# compat-routing pass
# ---------------------------------------------------------------------------

# device-only / version-mobile jax APIs that must be imported via
# h2o3_tpu/compat.py (module prefix -> why)
DEVICE_ONLY_APIS = {
    "jax.experimental.shard_map": "gone in the pinned jax — use "
                                  "compat.shard_map",
    "jax.shard_map": "use compat.shard_map",
    "jax.experimental.serialize_executable": "experimental API; loads "
                                             "must name their devices",
    "jax.experimental.pallas": "TPU kernel surface, imported at call "
                               "time only",
    "jax.profiler": "REST maps its errors to clean 4xx",
}
COMPAT_MODULE = "h2o3_tpu/compat.py"

# ---------------------------------------------------------------------------
# compile-ledger pass (ISSUE 12)
# ---------------------------------------------------------------------------

# the ONE chokepoint allowed to run `.lower(...).compile(` /
# `compat.compile_stablehlo` / `compile_cache.note_compile` — every XLA
# compile must land a ledger row (family, signature, duration, cache
# disposition, HBM estimate) or /3/Runtime and the compile-seconds
# series silently under-count. h2o3_genmodel/ is exempt like the compat
# pass: the standalone runners are framework-free by contract.
COMPILE_LEDGER_MODULES = ("h2o3_tpu/obs/compiles.py",)

# module prefixes where BARE `jax.jit` is banned outright (ISSUE 17):
# every jit in these subsystems must be a `compiles.ledgered_jit` so the
# compiles it triggers land under the subsystem's family. models/tree/
# predates the ledger (histogram.py's bare @jax.jit was the one compile
# family /3/Runtime couldn't see) — this scope closes that hole.
JIT_LEDGER_SCOPE = ("h2o3_tpu/models/tree/",)

# ---------------------------------------------------------------------------
# sync-hygiene pass
# ---------------------------------------------------------------------------

# modules whose except-pass handlers are findings (watchdog/supervisor
# tick paths: a silently-dead recovery loop is an outage multiplier)
SWALLOW_SCOPE = (
    "h2o3_tpu/parallel/watchdog.py",
    "h2o3_tpu/parallel/supervisor.py",
)

# ---------------------------------------------------------------------------
# registry passes (folded from tests/test_consistency.py)
# ---------------------------------------------------------------------------

# test files whose STRINGS deliberately contain armed-looking faultpoint /
# pickle / span text (analysis fixtures, this analyzer's own suite)
FAULTPOINT_SCAN_EXCLUDE = (
    "tests/test_analysis.py",
    "tests/test_consistency.py",
)

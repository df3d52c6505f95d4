"""Munge→score pipeline fusion: ONE program from raw columns to margins.

Reference: H2O-3 erases the feature-engineering/scoring boundary with the
MOJO pipeline + ``EasyPredictModelWrapper`` (PAPER.md L8) — the scorer
consumes RAW rows and the munging steps ride inside the scoring artifact.
Until this module the TPU port kept that boundary: a lazy Rapids feature
pipeline (rapids/planner.py) flushed into materialized Columns, and the
scoring session (scoring.py) re-packed those Columns into its feature
matrix — a full intermediate materialization plus packing pass between
two dispatch families, per request.

This module makes :class:`~h2o3_tpu.scoring.ScoringSession` a CONSUMER of
the planner DAG:

- **Capture.** When the frame offered to ``predict`` carries still-PENDING
  deferred Rapids outputs (lazy Columns of the session planner),
  :func:`try_capture` splices each pending expression tree — resolved over
  its SSA binding snapshot, exactly like the flush planner's inlining —
  into a single ``("pipe", feat_0, …, feat_{F-1})`` plan over the model's
  training feature order. Capture is READ-ONLY on the DAG: no node is
  observed, no Column materializes (``materialized_columns`` stays 0,
  counter-asserted by the consistency suite).
- **One program per row bucket.** The emitted program evaluates every
  feature expression (the same elementwise ``*_expr`` tracers the eager
  evaluator and the fusion engine share), packs the bucket window with
  the EXACT math of ``ShardedFrame.pack_features`` (pad → dynamic-slice →
  validity mask), and runs the model core — ``_fused_margins`` (forest
  bin+traverse) — in the SAME XLA program. Compile-ledger family
  ``pipeline``, riding the in-memory signature cache and the PR-6
  persistent compile cache: a warm restart compiles zero pipeline
  programs.
- **Bitwise contract.** Feature evaluation is row-local elementwise over
  the padded layout, so full-length-evaluate-then-window equals
  materialize-then-pack per row; features feed only comparisons inside
  the binning core, and rewrite-prone edges INSIDE a feature expression
  are split into their own cached sub-programs by the fusion engine's
  ``_split_rewrite_edges`` — the same discipline the staged path applies.
  Pipeline margins are therefore bitwise-identical to the staged
  lazy-flush→fused-score path (asserted over randomized seeds).
- **GLM.** :func:`try_glm_raw` is the linear-model twin: engineered
  numeric predictors evaluate as fused plans (device arrays — never a
  Column), and ONE ``pipeline``-family program runs the exact
  ``models/glm._glm_predict`` core (eta from codes and coefficients + linkinv)
  over them at the frame's padded length.

Anything capture cannot hold (pending sorts, domain-remapped or missing
predictors, ragged layouts, multi-process clouds) falls back to the
staged path unchanged — deferral, flush and eager replay keep their
exact semantics.
"""

from __future__ import annotations

import hashlib
import os
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from h2o3_tpu.core.frame import Column, Frame, T_CAT
from h2o3_tpu.rapids import fusion
from h2o3_tpu.rapids import planner as lazy_planner

# ---------------------------------------------------------------------------
# enable / force switches (same contract as fusion.enabled / planner.enabled)
# ---------------------------------------------------------------------------

_FORCE: Optional[bool] = None


def enabled() -> bool:
    """Master switch (H2O_TPU_PIPELINE_FUSION, default on). Requires both
    upstream engines: statement fusion (the emitter) and the lazy session
    planner (pending nodes to splice) — the latter is deterministically
    OFF on multi-process clouds, so pipeline splicing is too."""
    if _FORCE is False:
        return False
    if not (fusion.enabled() and lazy_planner.enabled()):
        return False
    if _FORCE is True:
        return True
    return os.environ.get("H2O_TPU_PIPELINE_FUSION", "1").lower() not in (
        "0", "false", "off")


class force:
    """Context manager pinning pipeline splicing on/off regardless of the
    env knob (bench A/B runs and the equivalence suite). Forcing ON still
    requires fusion + the lazy planner (there is nothing to splice
    without them)."""

    def __init__(self, on: bool):
        self._on = bool(on)
        self._prev: Optional[bool] = None

    def __enter__(self):
        global _FORCE
        self._prev = _FORCE
        _FORCE = self._on
        return self

    def __exit__(self, *exc):
        global _FORCE
        _FORCE = self._prev
        return False


# ---------------------------------------------------------------------------
# counters (the /3/ScoringMetrics `pipeline` block + h2o3_pipeline_* metrics)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_COUNTS = {
    "captures": 0,                 # frames spliced onto a model core
    "fused_dispatches": 0,         # pipeline program executions
    "spliced_nodes": 0,            # pending DAG nodes spliced (no Column)
    "materialized_columns": 0,     # spliced columns forced to materialize
    "fused_rows": 0,               # logical rows through pipeline programs
    "programs_compiled": 0,        # actual XLA compiles (family `pipeline`)
    "compile_cache_hits": 0,       # warm reuse (memory or disk tier)
    "fallbacks": 0,                # captures abandoned to the staged path
}


def _bump(key: str, n: int = 1) -> None:
    with _LOCK:
        _COUNTS[key] += int(n)


def counters() -> dict:
    with _LOCK:
        return dict(_COUNTS)


def reset_counters() -> None:
    with _LOCK:
        for k in _COUNTS:
            _COUNTS[k] = 0


# ---------------------------------------------------------------------------
# capture — splice pending DAG expressions into one ("pipe", ...) plan
# ---------------------------------------------------------------------------

class _PipelinePlanner(fusion._Planner):
    """fusion._Planner that splices PENDING deferred expression nodes
    (resolved over their SSA binding snapshots) instead of materializing
    them, and records the frame-level name of every concrete leaf (the
    raw-input schema a standalone pipeline artifact ships)."""

    def __init__(self, env, planner):
        super().__init__(env)
        self._lazy = planner                   # SessionPlanner or None
        self.spliced: set = set()              # id(node) of spliced nodes
        self.names_by_token: Dict[int, str] = {}

    def _pending_node(self, col: Column):
        if self._lazy is None:
            return None
        n = self._lazy.node_for_token(col.token)
        return n if (n is not None and n.state == "pending") else None

    def _splice(self, node):
        if node.kind != "expr":
            raise fusion._NotFusible          # pending sort/slice: staged
        self.spliced.add(id(node))
        env0 = self.env
        self.env = lazy_planner._SnapEnv(node.bindings)
        try:
            n, is_col = self.build(node.ast)
        finally:
            self.env = env0
        if not is_col:
            raise fusion._NotFusible
        return n

    def _bind_value(self, v):
        if isinstance(v, Frame) and v.ncols == 1:
            node = self._pending_node(v.col(0))
            if node is not None:
                return self._splice(node), True
        return super()._bind_value(v)

    def _frame_leaf(self, fr, name):
        col = fr.col(name)
        node = self._pending_node(col)
        if node is not None:
            return self._splice(node)
        return self._leaf_named(col, name)

    def _leaf_named(self, col: Column, name: str):
        leaf = self._leaf(col)
        prev = self.names_by_token.setdefault(col.token, name)
        if prev != name:                       # one column, two names: the
            self.names_by_token[col.token] = ""  # artifact schema refuses
        return leaf


class Capture:
    """One successful splice: the fused ("pipe", ...) plan plus the layout
    facts execution and export need. Holding it keeps the concrete leaf
    Columns (and nothing else) alive; the DAG itself stays pending."""

    __slots__ = ("plan", "padded", "nrows", "spliced", "names_by_token",
                 "feature_names")

    def __init__(self, plan, padded: int, nrows: int, spliced: int,
                 names_by_token: Dict[int, str],
                 feature_names: List[str]):
        self.plan = plan
        self.padded = int(padded)
        self.nrows = int(nrows)
        self.spliced = int(spliced)
        self.names_by_token = names_by_token
        self.feature_names = list(feature_names)


def _owning_planner(frame: Frame, names) -> Optional[tuple]:
    """(planner, n_pending) for the single live SessionPlanner ALL of the
    frame's pending feature columns belong to; None when no feature is
    pending (nothing to splice) or ownership is split."""
    owner = None
    n_pending = 0
    for name in names:
        if name not in frame:
            return None
        got = lazy_planner.pending_node_for_token(frame.col(name).token)
        if got is None:
            continue
        pl, _node = got
        if owner is not None and pl is not owner:
            return None
        owner = pl
        n_pending += 1
    if owner is None or n_pending == 0:
        return None
    return owner, n_pending


def _capture_pipe(frame: Frame, names, planner) -> Optional[Capture]:
    """Build the fused ("pipe", feat...) plan over `names` in order; every
    pending expression splices, every concrete column binds as a leaf.
    Returns None when any feature cannot enter one program."""
    pp = _PipelinePlanner(None, planner)
    feats = []
    try:
        for name in names:
            col = frame.col(name)
            node = pp._pending_node(col)
            feats.append(pp._splice(node) if node is not None
                         else pp._leaf_named(col, name))
    except fusion._NotFusible:
        return None
    p = pp.plan
    if p.padded is None or not pp.spliced:
        return None
    if p.nrows != frame.nrows:
        return None
    p.root = ("pipe",) + tuple(feats)
    p.out_name = "pipe"
    fusion._split_rewrite_edges(p)
    fusion._finish_signature(p)
    return Capture(p, p.padded, frame.nrows, len(pp.spliced),
                   dict(pp.names_by_token), list(names))


def try_capture(session, frame: Frame) -> Optional[Capture]:
    """Splice a (possibly lazy) frame onto a forest ScoringSession: a
    Capture when every training feature either IS a concrete
    exactly-matching column or a pending deferred expression, else None
    (the staged adapt→pack→score path is the contract). Read-only: no DAG
    node is observed, no Column materializes."""
    if not enabled():
        return None
    cap = capture_forest(session, frame)
    if cap is None:
        return None
    _bump("captures")
    _bump("spliced_nodes", cap.spliced)
    return cap


def capture_forest(session, frame: Frame) -> Optional[Capture]:
    """try_capture minus the serving knob and counters — the artifact
    exporter captures through this regardless of H2O_TPU_PIPELINE_FUSION."""
    spec = session.spec
    model = session.model
    got = _owning_planner(frame, spec.names)
    if got is None:
        return None
    planner, _n = got
    # metadata preflight: anything adapt_test would raise on (or NA-fill /
    # domain-remap) stays on the staged path, so errors surface there
    if model.check_test_compat(frame) is not None:
        return None
    domains = model._output.domains
    for name in spec.names:
        col = frame.col(name)
        train_dom = domains.get(name)
        if train_dom is not None:
            if col.ctype != T_CAT or list(col.domain or []) != \
                    list(train_dom):
                return None       # remap/unseen-domain: staged handles it
        elif col.ctype == T_CAT:
            return None
    with planner._lock:           # no concurrent flush mid-capture
        cap = _capture_pipe(frame, spec.names, planner)
    if cap is None:
        return None
    from h2o3_tpu.core.runtime import cluster

    cl = cluster()
    if cap.padded % max(cl.row_shards, 1) != 0:
        return None
    return cap


def note_fallback(cap: Capture) -> None:
    """A captured pipeline abandoned mid-execution: its spliced columns
    will now materialize through the staged path it falls back to."""
    _bump("fallbacks")
    _bump("materialized_columns", cap.spliced)


# ---------------------------------------------------------------------------
# compilation — family `pipeline`, signature cache + persistent tier
# ---------------------------------------------------------------------------

_PROGRAMS: Dict[str, Any] = {}
_PROG_LOCK = threading.Lock()
_PROG_CAP = 128


def clear_programs() -> None:
    """Drop the in-process pipeline program cache (tests simulate a cold
    restart against the persistent tier this way)."""
    with _PROG_LOCK:
        _PROGRAMS.clear()


def _emit_pipe(plan, bucket: int, max_depth: int, K: int):
    """Traceable (pos, n, *leaves, *consts, edges, is_cat, init,
    *forest) -> (bucket,) / (bucket, K) margins.

    Each array leaf windows FIRST with the EXACT ops of ShardedFrame's
    _pack_features_fn (pad → dynamic_slice → validity mask) and the
    features then evaluate at bucket length through the same elementwise
    tracers the eager evaluator and the fusion engine share. The spliced
    plan is elementwise by construction (reductions and rewrite-edge
    splits arrive as separate sub-program leaves), so every output lane
    sees exactly the inputs the staged materialize-then-pack path feeds
    it — a pipeline margin stays bitwise the staged margin while each
    bucket dispatch pays O(bucket) munge work instead of O(padded),
    which is what makes a chunked frame cheaper fused than staged.
    Bare column features cast with the packer's plain astype (NA_CAT
    codes stay negative and bin to the NA bin); features used INSIDE
    expressions convert through cat_to_f32_expr like every fused
    statement."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.models.tree.compressed import _fused_margins
    from h2o3_tpu.ops import elementwise as E

    n_leaf = len(plan.leaves)
    n_const = len(plan.consts)
    ctypes = list(plan.leaf_ctypes)
    feats = plan.root[1:]

    def run(pos, n, *args):
        consts = args[n_leaf:n_leaf + n_const]
        edges, is_cat, init = args[n_leaf + n_const: n_leaf + n_const + 3]
        forest = args[n_leaf + n_const + 3:]

        def window(x):
            if getattr(x, "ndim", 1) == 0:   # scalar sub-program leaf
                return x
            x = jnp.pad(x, (0, bucket))      # packer's out-of-bounds guard
            return jax.lax.dynamic_slice_in_dim(x, pos, bucket)

        leaves = [window(x) for x in args[:n_leaf]]

        def ev(node):
            k = node[0]
            if k == "L":
                d = leaves[node[1]]
                return (E.cat_to_f32_expr(d) if ctypes[node[1]] == T_CAT
                        else d)
            if k == "K":
                return consts[node[1]]
            if k == "bin":
                return E.binop_expr(node[1], ev(node[2]), ev(node[3]))
            if k == "log":
                return E.logical_expr(node[1], ev(node[2]), ev(node[3]))
            if k == "un":
                return E.unop_expr(node[1], ev(node[2]))
            if k == "ifelse":
                return E.ifelse_expr(ev(node[1]), ev(node[2]),
                                     ev(node[3]))
            if k == "isna":
                return E.isna_expr(ev(node[1]))
            raise AssertionError(f"bad pipeline node {k!r}")

        idx = pos + jnp.arange(bucket, dtype=jnp.int32)
        valid = idx < n
        parts = []
        for f in feats:
            x = (leaves[f[1]].astype(jnp.float32) if f[0] == "L"
                 else ev(f))
            parts.append(jnp.broadcast_to(x, (bucket,)))
        X = jnp.stack(parts, axis=-1)
        X = jnp.where(valid[:, None], X, jnp.float32(0))
        return _fused_margins(X, edges, is_cat, init, *forest,
                              max_depth, K)

    return run


def _get_program(full_sig: str, bucket: int, make_jfn, make_structs,
                 program: str):
    """Pipeline program for one signature: in-memory first, then the
    persistent compile cache, then an actual XLA compile recorded on the
    `pipeline` ledger family — the same three-tier discipline as the
    scoring and rapids families, so a warm restart compiles zero
    pipeline programs."""
    with _PROG_LOCK:
        prog = _PROGRAMS.get(full_sig)
    if prog is not None:
        _bump("compile_cache_hits")
        from h2o3_tpu.obs import compiles

        compiles.record_hit("pipeline", full_sig, "memory",
                            program=program)
        return prog

    from h2o3_tpu.artifact import compile_cache
    from h2o3_tpu.obs import compiles

    jfn = make_jfn()
    ckey = None
    exe = None
    if compile_cache.enabled():
        sig_hash = hashlib.sha256(full_sig.encode()).hexdigest()
        ckey = compile_cache.cache_key(sig_hash, bucket,
                                       variant="pipeline")
        exe = compile_cache.load(ckey)
        if exe is not None:
            _bump("compile_cache_hits")
            compiles.record_hit("pipeline", full_sig, "disk",
                                program=program)
    if exe is None:
        exe = compiles.compile_jit("pipeline", jfn, make_structs(),
                                   signature=full_sig, program=program)
        _bump("programs_compiled")
        if ckey is not None:
            compile_cache.store(ckey, exe)
    from h2o3_tpu.memory import budget as membudget

    membudget.note_compiled("pipeline", bucket, exe)
    prog = fusion._Program(exe, jfn)
    with _PROG_LOCK:
        if len(_PROGRAMS) >= _PROG_CAP:
            _PROGRAMS.pop(next(iter(_PROGRAMS)))
        _PROGRAMS[full_sig] = prog
    return prog


def _forest_program(session, cap: Capture, bucket: int):
    import jax

    plan = cap.plan
    K = session._out_k()
    full_sig = (f"pipe|{plan.signature}|m{session._model_checksum()}"
                f"|b{bucket}")

    def make_jfn():
        return jax.jit(_emit_pipe(plan, bucket,
                                  session.forest.max_depth, K))

    def make_structs():
        structs = [jax.ShapeDtypeStruct((), np.int32),
                   jax.ShapeDtypeStruct((), np.int32)]
        for i, leaf in enumerate(plan.leaves):
            if isinstance(leaf, fusion.Plan) and \
                    fusion._plan_is_scalar(leaf):
                structs.append(jax.ShapeDtypeStruct((), np.float32))
            else:
                structs.append(jax.ShapeDtypeStruct(
                    (plan.padded,), np.dtype(plan.leaf_dtypes[i])))
        structs += [jax.ShapeDtypeStruct((), np.float32)] * len(plan.consts)
        structs += [session._edges, session._is_cat, session._init]
        structs += list(session._arrays)
        return tuple(structs)

    return _get_program(full_sig, bucket, make_jfn, make_structs,
                        "pipeline_score")


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

def execute_margins(session, cap: Capture):
    """Dispatch the captured pipeline over the bucket ladder: returns
    (margins, n_dispatches) with margins ONE device array of the frame's
    exact logical rows — (n,) or (n, K). Sub-program leaves (rewrite-edge
    splits inside feature expressions) run first as their own cached
    rapids programs, exactly as the staged flush would run them."""
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.obs import tracing

    plan = cap.plan
    # colocate the raw-column leaves with the model constants ONCE per
    # capture: columns live row-sharded on the mesh but the bucket
    # programs are compiled for unsharded operands (the AOT/persistent-
    # cache contract), so a sharded leaf would force the cached
    # executable to reject its inputs and every dispatch to re-partition
    # under GSPMD — one explicit transfer per leaf here instead of one
    # implicit gather per bucket dispatch
    place = getattr(session._edges, "sharding", None)

    def _leaf(l):
        x = fusion._run_program(l) if isinstance(l, fusion.Plan) else l.data
        if place is not None and getattr(x, "sharding", place) != place:
            x = jax.device_put(x, place)
        return x

    leaf_args = [_leaf(l) for l in plan.leaves]
    const_args = [fusion._const_arg(v) for v in plan.consts]
    model_args = ((session._edges, session._is_cat, session._init)
                  + tuple(session._arrays))
    n = cap.nrows
    maxb = session.buckets[-1]
    n_disp = 0

    def window(pos: int, m: int):
        nonlocal n_disp
        bucket = session._bucket_for(m)
        prog = _forest_program(session, cap, bucket)
        args = ((jnp.int32(pos), jnp.int32(n)) + tuple(leaf_args)
                + tuple(const_args) + model_args)
        with tracing.span("dispatch", bucket=bucket, rows=m,
                          path="pipeline"):
            try:
                out = prog.exe(*args)
            except Exception as e:   # noqa: BLE001 — AOT placement
                from h2o3_tpu.memory import stream as _stream

                if _stream.is_oom(e):
                    raise
                out = prog.jfn(*args)
        n_disp += 1
        _bump("fused_dispatches")
        from h2o3_tpu import scoring

        scoring.note_dispatch("pipeline")
        return out[:m]

    from h2o3_tpu.memory import stream

    # windows already pay O(bucket) munge work (the leaves window inside
    # the program) — the planner only caps how many rows ride each one
    outs: List[Any] = stream.run_windows(
        "pipeline", n, window, maxb,
        row_bytes=4.0 * (2 * max(len(plan.leaves), 1)
                         + len(session.spec.names) + session._out_k()),
        window_sizer=session._window_snap)
    _bump("fused_rows", n)
    if not outs:
        K = session._out_k()
        return jnp.zeros((0,) if K == 1 else (0, K), jnp.float32), 0
    return (outs[0] if len(outs) == 1 else jnp.concatenate(outs)), n_disp


# ---------------------------------------------------------------------------
# GLM — engineered predictors as fused plans + ONE linear-predictor program
# ---------------------------------------------------------------------------

def _glm_checksum(model) -> str:
    ck = getattr(model, "_pipeline_ck", None)
    if ck is None:
        from h2o3_tpu.artifact import glm as artifact_glm

        ck = model._pipeline_ck = artifact_glm.glm_checksum(model)
    return ck


def glm_eligible(model, frame: Frame) -> Optional[str]:
    """None when `model` can splice over `frame`; else the reason (shared
    by the in-process path and the pipeline artifact exporter)."""
    from h2o3_tpu.models.glm import GLMModel

    if not isinstance(model, GLMModel):
        return f"{type(model).__name__} is not a GLM"
    d = model.dinfo
    if d is None or model.beta is None:
        return "model has no trained coefficients"
    if model.linkname == "ordinal":
        return "ordinal GLMs stay on the staged path"
    if model._parms.get("interactions"):
        return "GLMs with interaction columns expand frames at adapt time"
    oc = model._parms.get("offset_column")
    if oc and oc in frame:
        return "per-request offsets stay on the staged path"
    for name in d.cat_names:
        if name not in frame:
            return f"categorical predictor {name!r} missing"
        col = frame.col(name)
        if col.ctype != T_CAT or list(col.domain or []) != \
                list(d.domains.get(name) or []):
            return f"categorical predictor {name!r} needs domain adaptation"
    for name in d.num_names:
        if name not in frame:
            return f"numeric predictor {name!r} missing"
        if frame.col(name).ctype == T_CAT:
            return f"predictor {name!r} was numeric in training"
    return None


def _glm_feature_plans(model, frame: Frame) -> Optional[tuple]:
    """Per-predictor (dinfo order) list of concrete Columns / fused Plans
    for the engineered ones, or None when nothing is pending or a pending
    predictor cannot fuse."""
    d = model.dinfo
    got = _owning_planner(frame, d.predictor_names)
    if got is None:
        return None
    planner, _n = got
    entries: List[tuple] = []
    padded = None
    spliced = 0
    with planner._lock:
        for name in d.predictor_names:
            col = frame.col(name)
            node = planner.node_for_token(col.token)
            if node is not None and node.state == "pending":
                pp = _PipelinePlanner(
                    lazy_planner._SnapEnv(node.bindings), planner)
                try:
                    root = pp._splice(node)
                except fusion._NotFusible:
                    return None
                p = pp.plan
                if p.padded is None or p.nrows != frame.nrows:
                    return None
                p.root = root
                p.out_name = name
                fusion._split_rewrite_edges(p)
                fusion._finish_signature(p)
                if padded is None:
                    padded = p.padded
                elif padded != p.padded:
                    return None
                spliced += max(len(pp.spliced), 1)
                entries.append(("plan", p))
            else:
                dcol = col.data
                if dcol is None:
                    return None
                if padded is None:
                    padded = int(dcol.shape[0])
                elif padded != int(dcol.shape[0]):
                    return None
                entries.append(("col", col))
    if spliced == 0:
        return None
    return entries, padded, spliced


def try_glm_raw(model, frame: Frame) -> Optional[dict]:
    """Raw prediction dict (`probs`/`value` at padded length, like
    ``GLMModel._predict_raw``) for a GLM fed by a pending lazy feature
    pipeline, computed WITHOUT materializing any engineered Column: each
    fused feature plan dispatches device-to-device, then one
    ``pipeline``-family program runs the exact ``_glm_predict`` core.
    None → caller stays on the staged path."""
    if not enabled():
        return None
    if glm_eligible(model, frame) is not None:
        return None
    got = _glm_feature_plans(model, frame)
    if got is None:
        return None
    entries, padded, spliced = got
    import jax
    import jax.numpy as jnp

    from h2o3_tpu.core import sharded_frame
    from h2o3_tpu.obs import tracing

    d = model.dinfo
    K = int(model._output.nclasses)
    # same colocation contract as execute_margins: the cached executable
    # is compiled for unsharded operands, so row-sharded column leaves
    # transfer once to the coefficient placement instead of forcing a
    # GSPMD re-partition on every dispatch
    place = getattr(model.beta, "sharding", None)
    arrays = []
    dtypes = []
    for kind, v in entries:
        if kind == "plan":
            arr = fusion._run_program(v)
        else:
            arr = v.data
        if place is not None and getattr(arr, "sharding", place) != place:
            arr = jax.device_put(arr, place)
        arrays.append(arr)
        dtypes.append(str(arr.dtype))
    full_sig = (f"glm|{_glm_checksum(model)}|r{padded}"
                f"|{','.join(dtypes)}")

    def make_jfn():
        from h2o3_tpu.models.glm import _glm_predict

        def run(offset, beta, *arrs):
            return _glm_predict(
                tuple(arrs), beta, offset, dinfo=d,
                linkname=model.linkname,
                link_power=(model.link_power if K <= 2 else 0.0),
                nclasses=K if K > 2 else 1)

        return jax.jit(run)

    def make_structs():
        structs = [jax.ShapeDtypeStruct((), np.float32),
                   jax.ShapeDtypeStruct(np.asarray(model.beta).shape,
                                        np.float32)]
        structs += [jax.ShapeDtypeStruct((padded,), np.dtype(dt))
                    for dt in dtypes]
        return tuple(structs)

    prog = _get_program(full_sig, padded, make_jfn, make_structs,
                        "pipeline_glm")
    args = (jnp.float32(0.0), model.beta) + tuple(arrays)
    with tracing.span("dispatch", rows=cap_rows(frame), path="pipeline"):
        try:
            out = prog.exe(*args)
        except Exception:   # noqa: BLE001 — AOT placement mismatch
            out = prog.jfn(*args)
    _bump("captures")
    _bump("spliced_nodes", spliced)
    _bump("fused_dispatches")
    _bump("fused_rows", frame.nrows)
    from h2o3_tpu import scoring

    scoring.note_dispatch("pipeline")
    sharded_frame.note_packed(frame.nrows)
    if K > 2:
        return {"probs": out}
    if K == 2:
        # the exact EAGER post-op _predict_raw applies outside its program
        return {"probs": jnp.stack([1 - out, out], axis=-1)}
    return {"value": out}


def cap_rows(frame: Frame) -> int:
    return int(frame.nrows)


# ---------------------------------------------------------------------------
# stats (the /3/ScoringMetrics `pipeline` block)
# ---------------------------------------------------------------------------

def stats() -> dict:
    out = counters()
    with _PROG_LOCK:
        out["cached_programs"] = len(_PROGRAMS)
    out["enabled"] = enabled()
    return out

"""Pipeline artifact exporter: munge→score as ONE standalone program.

The MOJO-pipeline story (PAPER.md §L8) for the AOT lineage: where a
forest/GLM artifact scores TRAINING-SHAPED feature rows, a *pipeline*
artifact (manifest ``model_type="pipeline"``) ships the captured Rapids
feature plan fused with the model core, so ``h2o3_genmodel.aot`` scores
RAW untransformed rows — the engineered features are computed inside the
same XLA program as the bin+traverse (forest) or expand+matmul+linkinv
(GLM) core, bitwise-identical to in-process pipeline serving.

Everything rides the existing artifact container: sha256-gated payloads,
per-bucket AOT executable + StableHLO fallback, single-device lowering.
The plan itself (SSA snapshot of the spliced expression trees) is written
as ``pipeline.json`` — the auditable record of WHAT was fused; the
runner never interprets it, it executes the shipped program.

Export refuses what cannot be reproduced bitwise in one program:

- feature expressions containing compiler-rewrite boundaries (``/ ^ %
  intDiv``, or a multiply feeding an add/sub) — in-process these split
  into separate cached sub-programs, and fusing them into one standalone
  lowering would license exactly the FMA/reassociation rewrites the
  split exists to prevent;
- raw inputs that are not float32 numerics or integer-coded
  categoricals (the
  raw-row packer produces float32; integer-typed numeric columns take
  a different arithmetic path in-process);
- unnamed or name-colliding leaf columns (the raw-row schema must be a
  plain name→column mapping).
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Any, Dict, List, Optional

import numpy as np

from h2o3_tpu.artifact import aot, manifest, packer
from h2o3_tpu.artifact.manifest import ArtifactError
from h2o3_tpu.core.frame import T_CAT
from h2o3_tpu.rapids import fusion

PIPELINE_FILE = "pipeline.json"


# ---------------------------------------------------------------------------
# capture + eligibility
# ---------------------------------------------------------------------------

def capture_for_export(model, frame):
    """(Capture, inner) for a model over a frame carrying a PENDING lazy
    feature pipeline; raises ArtifactError with the refusal reason."""
    from h2o3_tpu import pipeline as pl
    from h2o3_tpu.models.glm import GLMModel

    if isinstance(model, GLMModel):
        from h2o3_tpu.artifact.glm import supports_glm_export

        why = supports_glm_export(model) or pl.glm_eligible(model, frame)
        if why:
            raise ArtifactError(f"cannot export pipeline for {model.key}: "
                                f"{why}")
        d = model.dinfo
        got = pl._owning_planner(frame, d.predictor_names)
        if got is None:
            raise ArtifactError(
                f"cannot export pipeline for {model.key}: the frame "
                "carries no pending lazy Rapids feature for this model's "
                "predictors (export BEFORE anything observes the deferred "
                "columns)")
        planner, _n = got
        with planner._lock:
            cap = pl._capture_pipe(frame, d.predictor_names, planner)
        if cap is None:
            raise ArtifactError(
                f"cannot export pipeline for {model.key}: a pending "
                "feature does not fuse (sorts/slices and non-fusible ops "
                "stay on the staged path)")
        return cap, "glm"

    from h2o3_tpu import scoring

    if not scoring.supports(model):
        raise ArtifactError(
            f"cannot export pipeline for {model.key}: not a fused-path "
            "forest model (GBM/DRF/XGBoost) or GLM")
    session = scoring.session_for(model)
    cap = pl.capture_forest(session, frame)
    if cap is None:
        raise ArtifactError(
            f"cannot export pipeline for {model.key}: the frame does not "
            "splice onto the model (needs >= 1 pending lazy Rapids "
            "feature, concrete columns matching the training schema "
            "exactly, and a fusible expression per engineered feature)")
    return cap, "forest"


def check_exportable(cap) -> None:
    """Refuse captures whose one-program lowering could not be bitwise."""
    plan = cap.plan
    for leaf in plan.leaves:
        if isinstance(leaf, fusion.Plan):
            raise ArtifactError(
                "pipeline features contain compiler-rewrite boundaries "
                "(/ ^ % intDiv, or a multiply feeding an add/sub); "
                "in-process these run as separate programs and cannot be "
                "fused bitwise into one standalone program — simplify the "
                "feature expressions or precompute those terms")
    names = []
    for i, leaf in enumerate(plan.leaves):
        nm = cap.names_by_token.get(leaf.token)
        if not nm:
            raise ArtifactError(
                "every raw input of a pipeline artifact must be a "
                "uniquely-named frame column (an unnamed or ambiguously "
                "named leaf cannot enter the raw-row schema)")
        names.append(nm)
        dt = str(plan.leaf_dtypes[i])
        if plan.leaf_ctypes[i] == T_CAT:
            # code width is immaterial: codes only feed comparisons and
            # table gathers, so int8 in-process == int32 in the artifact
            if not dt.startswith("int"):
                raise ArtifactError(
                    f"categorical input {nm!r} has dtype {dt}; pipeline "
                    "artifacts require integer level codes")
        elif dt != "float32":
            raise ArtifactError(
                f"numeric input {nm!r} has dtype {dt}; pipeline artifacts "
                "score float32 raw rows, and integer-typed columns take a "
                "different arithmetic path in-process — cast the source "
                "column to real first")
    if len(set(names)) != len(names):
        raise ArtifactError(
            "two distinct raw input columns share a name — the raw-row "
            f"schema must be unambiguous (inputs: {names})")


# ---------------------------------------------------------------------------
# plan snapshot (pipeline.json) — the auditable SSA record
# ---------------------------------------------------------------------------

def _tree_json(node):
    if isinstance(node, tuple):
        return [_tree_json(c) for c in node]
    return node


def _inputs_of(cap) -> List[Dict[str, Any]]:
    plan = cap.plan
    out = []
    for i, leaf in enumerate(plan.leaves):
        nm = cap.names_by_token.get(leaf.token)
        cat = plan.leaf_ctypes[i] == T_CAT
        out.append({"name": nm, "kind": "cat" if cat else "num",
                    "domain": list(leaf.domain or []) if cat else None})
    return out


def _plan_payload(cap, inner: str) -> bytes:
    plan = cap.plan
    doc = {
        "inner": inner,
        "signature": plan.signature,
        "root": _tree_json(plan.root),
        "inputs": _inputs_of(cap),
        "consts": [float(v) for v in plan.consts],
        "spliced_nodes": int(cap.spliced),
    }
    return json.dumps(doc, indent=1, sort_keys=True).encode("utf-8")


# ---------------------------------------------------------------------------
# lowering — feature plan + model core in one single-device program
# ---------------------------------------------------------------------------

def _scorer_fn(cap, inner: str, model):
    """run(Xr, offset) over a (bucket, R) float32 raw matrix: re-derive
    typed leaf columns (cat codes via the same NaN→-1 rule the raw-row
    packer uses), evaluate every feature expression with the shared
    elementwise tracers, and run the model core — constants baked in, so
    the standalone runner needs no device arguments."""
    import jax.numpy as jnp

    from h2o3_tpu.ops import elementwise as E

    plan = cap.plan
    ctypes = list(plan.leaf_ctypes)
    feats = plan.root[1:]
    const_dev = [jnp.float32(float(v)) for v in plan.consts]

    if inner == "forest":
        arrays = packer.pack_forest(model.forest, model.spec)
        meta = packer.forest_meta(model.forest, model.spec)
        edges, is_cat, fargs = packer.scoring_inputs(
            arrays, meta["max_depth"])
        init = (arrays["init_class"] if "init_class" in arrays
                else np.float32(meta["init_f"]))
        edges_c = jnp.asarray(edges)
        is_cat_c = jnp.asarray(is_cat)
        init_c = jnp.asarray(init)
        fargs_c = tuple(jnp.asarray(a) for a in fargs)
        max_depth = int(meta["max_depth"])
        K = (int(meta["nclasses"])
             if (int(meta["nclasses"]) > 2 or meta["per_class_trees"])
             else 1)
    else:
        d = model.dinfo
        beta_c = jnp.asarray(np.asarray(model.beta, np.float32))
        K = int(model._output.nclasses)
        catset = set(d.cat_names)
        pred_names = list(d.predictor_names)

    def run(Xr, offset):
        cols = []
        for i, ct in enumerate(ctypes):
            x = Xr[:, i]
            cols.append(jnp.where(jnp.isnan(x), -1.0, x)
                        .astype(jnp.int32) if ct == T_CAT else x)

        def ev(node):
            k = node[0]
            if k == "L":
                c = cols[node[1]]
                return (E.cat_to_f32_expr(c)
                        if ctypes[node[1]] == T_CAT else c)
            if k == "K":
                return const_dev[node[1]]
            if k == "bin":
                return E.binop_expr(node[1], ev(node[2]), ev(node[3]))
            if k == "log":
                return E.logical_expr(node[1], ev(node[2]), ev(node[3]))
            if k == "un":
                return E.unop_expr(node[1], ev(node[2]))
            if k == "ifelse":
                return E.ifelse_expr(ev(node[1]), ev(node[2]), ev(node[3]))
            if k == "isna":
                return E.isna_expr(ev(node[1]))
            raise AssertionError(f"bad pipeline node {k!r}")

        if inner == "forest":
            from h2o3_tpu.models.tree.compressed import _fused_margins

            parts = [cols[f[1]].astype(jnp.float32) if f[0] == "L"
                     else ev(f) for f in feats]
            X = jnp.stack(parts, axis=-1)
            return _fused_margins(X, edges_c, is_cat_c, init_c, *fargs_c,
                                  max_depth, K)

        from h2o3_tpu.models.glm import _glm_predict

        arrs = []
        for i, name in enumerate(pred_names):
            f = feats[i]
            if name in catset:
                arrs.append(cols[f[1]])        # int32 codes, concrete
            else:
                arrs.append(cols[f[1]] if f[0] == "L" else ev(f))
        return _glm_predict(
            tuple(arrs), beta_c, offset, dinfo=d,
            linkname=model.linkname,
            link_power=(model.link_power if K <= 2 else 0.0),
            nclasses=K if K > 2 else 1)

    return run


def compile_pipeline_bucket(bucket: int, cap, inner: str, model,
                            sig_hash: str):
    """AOT-compile one bucket of the fused pipeline; returns (compiled,
    blob, stablehlo_text, kept_arg_indices_or_None)."""
    import jax

    from h2o3_tpu.obs import compiles

    R = len(cap.plan.leaves)
    fn = jax.jit(_scorer_fn(cap, inner, model))
    lowered = fn.lower(
        jax.ShapeDtypeStruct((int(bucket), R), np.float32),
        jax.ShapeDtypeStruct((), np.float32))
    text = lowered.as_text()
    compiled = compiles.compile_lowered(
        "artifact", lowered,
        signature=("artifact_pipeline", int(bucket), inner, sig_hash),
        program=f"artifact_pipeline_bucket_{int(bucket)}")
    return (compiled, aot.serialize_exec_blob(compiled), text,
            aot.kept_arg_indices(compiled, text, 2))


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def export_pipeline(model, frame, out_dir: str,
                    buckets: Optional[List[int]] = None) -> Dict[str, Any]:
    """Export the lazy feature pipeline feeding `frame` fused with
    `model` as a standalone pipeline artifact; returns the manifest.
    Capture is read-only — the pending DAG survives the export and the
    frame can still be scored in-process afterwards."""
    from h2o3_tpu.artifact import export as model_export
    from h2o3_tpu.artifact import glm as artifact_glm
    from h2o3_tpu.models.glm import GLMModel

    cap, inner = capture_for_export(model, frame)
    check_exportable(cap)
    buckets = sorted({int(b) for b in
                      (buckets or model_export.default_buckets())
                      if int(b) > 0})
    if not buckets:
        raise ArtifactError("at least one positive row bucket is required")
    os.makedirs(out_dir, exist_ok=True)

    if inner == "glm":
        inner_checksum = artifact_glm.glm_checksum(model)
        model_arrays = artifact_glm.pack_glm(model)
        model_file = ("glm", artifact_glm.GLM_FILE)
        o = model._output
        cat = o.model_category
        post = {"kind": ("glm_binomial" if cat == "Binomial"
                         else "glm_multinomial" if cat == "Multinomial"
                         else "glm_regression")}
        nclasses = int(artifact_glm.glm_meta(model)["nclasses"])
        per_class, max_depth, init_f, n_trees = False, 0, 0.0, 0
    else:
        inner_checksum = packer.model_checksum(model.forest, model.spec)
        model_arrays = packer.pack_forest(model.forest, model.spec)
        model_file = ("forest", model_export.FOREST_FILE)
        meta = packer.forest_meta(model.forest, model.spec)
        o = model._output
        post = model_export._post_spec(model)
        nclasses = int(meta["nclasses"])
        per_class = bool(meta["per_class_trees"])
        max_depth = int(meta["max_depth"])
        init_f = float(meta["init_f"])
        n_trees = int(meta["n_trees"])

    sig_hash = hashlib.sha256(
        (inner_checksum + "|" + cap.plan.signature).encode()).hexdigest()
    plan_entry = manifest.write_payload(out_dir, PIPELINE_FILE,
                                        _plan_payload(cap, inner))
    model_entry = manifest.write_payload(out_dir, model_file[1],
                                         packer.dump_npz(model_arrays))
    fingerprint = aot.backend_fingerprint(single_device=True)
    execs, hlos = [], []
    for b in buckets:
        _compiled, blob, text, kept = compile_pipeline_bucket(
            b, cap, inner, model, sig_hash)
        e = manifest.write_payload(out_dir, f"exec_b{b}.bin", blob)
        e.update(bucket=b, backend=fingerprint)
        execs.append(e)
        h = manifest.write_payload(out_dir, f"hlo_b{b}.mlir",
                                   text.encode("utf-8"))
        h.update(bucket=b, kept_args=kept)
        hlos.append(h)

    inputs = _inputs_of(cap)
    names = [i["name"] for i in inputs]
    domains = {i["name"]: list(i["domain"]) for i in inputs
               if i["kind"] == "cat"}
    m = manifest.new_manifest(
        model_type="pipeline",
        algo=str(model.algo_name),
        model_key=str(model.key),
        model_category=str(o.model_category),
        model_checksum=sig_hash,
        nclasses=nclasses,
        per_class_trees=per_class,
        max_depth=max_depth,
        init_f=init_f,
        n_trees=n_trees,
        names=names,
        response_name=o.response_name,
        response_domain=list(o.response_domain or []) or None,
        domains=domains,
        post=post,
        default_threshold=model_export._default_threshold(model),
        pipeline={
            "inner": inner,
            "inputs": inputs,
            "signature": cap.plan.signature,
            "spliced_nodes": int(cap.spliced),
            "inner_model_checksum": inner_checksum,
        },
        glm=(artifact_glm.glm_meta(model)
             if isinstance(model, GLMModel) else None) or {},
        files={"pipeline": plan_entry, model_file[0]: model_entry},
        buckets=buckets,
        executables=execs,
        stablehlo=hlos,
    )
    manifest.write_manifest(out_dir, m)
    from h2o3_tpu.utils import timeline

    timeline.record("artifact", "export_pipeline", model=str(model.key),
                    dir=out_dir, buckets=len(buckets),
                    executables=len(execs), inner=inner,
                    spliced=int(cap.spliced))
    return m

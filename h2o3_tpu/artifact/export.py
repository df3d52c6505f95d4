"""Artifact exporter: trained model -> self-contained AOT scoring dir.

The MOJO2-for-TPU (PAPER.md §2.9 deployment story): a directory holding

- ``manifest.json``    — versioned, schema-validated, checksums for all
- ``forest.npz``       — packed forest + BinSpec constants (no pickle)
- ``exec_b{N}.bin``    — AOT-compiled fused scoring executable per row
                         bucket (single-device lowering; loadable only on
                         a matching backend fingerprint)
- ``hlo_b{N}.mlir``    — the SAME lowering as StableHLO text: the portable
                         fallback any jax/XLA target can compile

that the thin runner (``h2o3_genmodel.aot``) scores from with ZERO
training-stack imports. Export is coordinator-local: lowering/compiling
runs no collectives, so it is safe without an oplog broadcast.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

import numpy as np

from h2o3_tpu.artifact import aot, manifest, packer
from h2o3_tpu.artifact.manifest import ArtifactError

FOREST_FILE = "forest.npz"


def supports_export(model) -> Optional[str]:
    """None when `model` can be exported (the fused-path forest family,
    or a standalone-scorable GLM — the first non-forest class); otherwise
    the reason string. Structural check only — export does not care
    whether the serving fast path is env-enabled right now."""
    from h2o3_tpu.artifact.glm import supports_glm_export
    from h2o3_tpu.models.glm import GLMModel
    from h2o3_tpu.models.tree.shared_tree import SharedTreeModel

    if isinstance(model, GLMModel):
        return supports_glm_export(model)
    if not isinstance(model, SharedTreeModel):
        return (f"{type(model).__name__} is not a SharedTree forest model "
                "or a GLM; AOT artifacts cover the fused scoring family "
                "(GBM/DRF/XGBoost) and GLM — use MOJO export for other "
                "algos")
    if model.forest is None or model.spec is None:
        return "model has no trained forest"
    if type(model)._predict_raw is not SharedTreeModel._predict_raw:
        return (f"{type(model).__name__} overrides _predict_raw (custom "
                "post-processing) and cannot ride the fused program")
    return None


def _post_spec(model) -> Dict[str, Any]:
    """Margin -> raw post-processing recipe the runner replays with the
    identical jnp ops as SharedTreeModel._margin_to_raw."""
    from h2o3_tpu.models.model import ModelCategory

    cat = model._output.model_category
    if cat == ModelCategory.Binomial:
        return {"kind": "binomial"}
    if cat == ModelCategory.Multinomial:
        return {"kind": "multinomial"}
    dist = getattr(model, "_distribution", None)
    name = getattr(dist, "name", "gaussian") if dist is not None else \
        "gaussian"
    linkinv = "exp" if name in ("poisson", "gamma", "tweedie") else "identity"
    return {"kind": "regression", "linkinv": linkinv}


def _default_threshold(model) -> float:
    tm = model._output.training_metrics
    aucd = getattr(tm, "auc_data", None)
    return float(aucd.max_f1_threshold) if aucd is not None else 0.5


def default_buckets() -> List[int]:
    from h2o3_tpu.scoring import _env_buckets

    return sorted(_env_buckets())


def _export_glm(model, out_dir: str, buckets: List[int]) -> Dict[str, Any]:
    """GLM artifact (model_type="glm"): packed coefficients/moments npz +
    an AOT-compiled fused expand+matmul+linkinv program per row bucket
    (+ StableHLO fallback) — the first non-forest class through this
    exporter. Forest-specific manifest keys carry inert defaults so ONE
    schema covers both classes."""
    from h2o3_tpu.artifact import aot, glm

    arrays = glm.pack_glm(model)
    meta = glm.glm_meta(model)
    checksum = glm.glm_checksum(model)
    entry = manifest.write_payload(out_dir, glm.GLM_FILE,
                                   packer.dump_npz(arrays))
    fingerprint = aot.backend_fingerprint(single_device=True)
    execs, hlos = [], []
    for b in buckets:
        _compiled, blob, text, kept = glm.compile_glm_bucket(b, model)
        e = manifest.write_payload(out_dir, f"exec_b{b}.bin", blob)
        e.update(bucket=b, backend=fingerprint)
        execs.append(e)
        h = manifest.write_payload(out_dir, f"hlo_b{b}.mlir",
                                   text.encode("utf-8"))
        h.update(bucket=b, kept_args=kept)
        hlos.append(h)

    o = model._output
    cat = o.model_category
    post = {"kind": ("glm_binomial" if cat == "Binomial"
                     else "glm_multinomial" if cat == "Multinomial"
                     else "glm_regression")}
    names = list(model.dinfo.predictor_names)
    m = manifest.new_manifest(
        model_type="glm",
        algo=str(model.algo_name),
        model_key=str(model.key),
        model_category=str(cat),
        model_checksum=checksum,
        nclasses=int(meta["nclasses"]),
        per_class_trees=False,
        max_depth=0,
        init_f=0.0,
        n_trees=0,
        names=names,
        response_name=o.response_name,
        response_domain=list(o.response_domain or []) or None,
        domains={k: list(v) for k, v in model.dinfo.domains.items()},
        post=post,
        default_threshold=_default_threshold(model),
        glm=meta,
        files={"glm": entry},
        buckets=buckets,
        executables=execs,
        stablehlo=hlos,
    )
    manifest.write_manifest(out_dir, m)
    from h2o3_tpu.utils import timeline

    timeline.record("artifact", "export", model=str(model.key),
                    dir=out_dir, buckets=len(buckets),
                    executables=len(execs))
    return m


def export_model(model, out_dir: str,
                 buckets: Optional[List[int]] = None) -> Dict[str, Any]:
    """Write the artifact directory for `model`; returns the manifest."""
    why = supports_export(model)
    if why:
        raise ArtifactError(f"cannot export {model.key}: {why}")
    buckets = sorted({int(b) for b in (buckets or default_buckets())
                      if int(b) > 0})
    if not buckets:
        raise ArtifactError("at least one positive row bucket is required")
    os.makedirs(out_dir, exist_ok=True)
    from h2o3_tpu.models.glm import GLMModel

    if isinstance(model, GLMModel):
        return _export_glm(model, out_dir, buckets)

    forest, spec = model.forest, model.spec
    arrays = packer.pack_forest(forest, spec)
    meta = packer.forest_meta(forest, spec)
    checksum = packer.model_checksum(forest, spec)
    forest_entry = manifest.write_payload(out_dir, FOREST_FILE,
                                          packer.dump_npz(arrays))

    edges, is_cat, forest_args = packer.scoring_inputs(
        arrays, meta["max_depth"])
    init = (arrays["init_class"] if "init_class" in arrays
            else np.float32(meta["init_f"]))
    fingerprint = aot.backend_fingerprint(single_device=True)
    execs, hlos = [], []
    for b in buckets:
        _compiled, blob, text, kept = aot.compile_bucket(
            b, meta, edges, is_cat, init, forest_args)
        e = manifest.write_payload(out_dir, f"exec_b{b}.bin", blob)
        e.update(bucket=b, backend=fingerprint)
        execs.append(e)
        h = manifest.write_payload(out_dir, f"hlo_b{b}.mlir",
                                   text.encode("utf-8"))
        h.update(bucket=b, kept_args=kept)
        hlos.append(h)

    o = model._output
    m = manifest.new_manifest(
        algo=str(model.algo_name),
        model_key=str(model.key),
        model_category=str(o.model_category),
        model_checksum=checksum,
        nclasses=int(meta["nclasses"]),
        per_class_trees=bool(meta["per_class_trees"]),
        max_depth=int(meta["max_depth"]),
        init_f=float(meta["init_f"]),
        n_trees=int(meta["n_trees"]),
        names=list(o.names),
        response_name=o.response_name,
        response_domain=list(o.response_domain or []) or None,
        domains={k: list(v) for k, v in (o.domains or {}).items()},
        post=_post_spec(model),
        default_threshold=_default_threshold(model),
        distribution={
            "name": getattr(getattr(model, "_distribution", None), "name",
                            None),
            "tweedie_power": float(getattr(
                getattr(model, "_distribution", None), "power", 1.5)),
        },
        files={"forest": forest_entry},
        forest_args=list(packer.WALK_ARGS),
        buckets=buckets,
        executables=execs,
        stablehlo=hlos,
    )
    manifest.write_manifest(out_dir, m)
    from h2o3_tpu.utils import timeline

    timeline.record("artifact", "export", model=str(model.key),
                    dir=out_dir, buckets=len(buckets),
                    executables=len(execs))
    return m

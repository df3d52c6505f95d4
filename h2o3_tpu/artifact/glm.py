"""GLM artifact packing + AOT lowering — the first non-forest artifact
class (ROADMAP item 2c starter).

The exported program IS ``models/glm._glm_predict`` — the exact jit
program in-process serving runs (DataInfo.expand's impute/one-hot/
standardize, the intercept-augmented matmul, the linkinv) lowered per row
bucket over per-column inputs (int32 categorical codes, float32 numerics,
NA as negative/NaN). Bitwise identity to ``GLMModel.predict`` is by
construction, not re-implementation; the DataInfo moments are program
constants, beta rides as an argument from the npz payload.

Scope (refused with a clear reason otherwise): gaussian-family regression,
binomial and multinomial GLMs without interactions, offset columns or the
ordinal link — the shapes the expand/matmul/linkinv program covers
standalone.
"""

from __future__ import annotations

import hashlib
import json
from typing import Any, Dict, Optional, Tuple

import numpy as np

GLM_FILE = "glm.npz"


def supports_glm_export(model) -> Optional[str]:
    """None when `model` is an exportable GLM; otherwise the reason."""
    from h2o3_tpu.models.glm import GLMModel

    if not isinstance(model, GLMModel):
        return f"{type(model).__name__} is not a GLM"
    if model.beta is None or model.dinfo is None:
        return "model has no trained coefficients"
    if model.linkname == "ordinal":
        return "ordinal GLMs are not artifact-exportable yet"
    if model._parms.get("interactions"):
        return ("GLMs with interaction columns expand frames at adapt "
                "time and cannot ride the standalone program")
    if model._parms.get("offset_column"):
        return ("GLMs with an offset column need per-request offsets the "
                "standalone artifact cannot carry")
    return None


def pack_glm(model) -> Dict[str, np.ndarray]:
    """Dense arrays for a trained GLM — the whole payload is arrays
    (allow_pickle=False end to end, like the forest npz)."""
    d = model.dinfo
    return {
        "beta": np.asarray(model.beta, np.float32),
        "cat_modes": np.asarray(d.cat_modes, np.int32),
        "impute_values": np.asarray(d.impute_values, np.float32),
        "num_means": np.asarray(d.num_means, np.float32),
        "num_sigmas": np.asarray(d.num_sigmas, np.float32),
        "cards": np.asarray(d.cards, np.int64),
    }


def glm_meta(model) -> Dict[str, Any]:
    """The static (shape-defining) configuration the fused program is
    specialized on; rides in the manifest's ``glm`` block."""
    d = model.dinfo
    return {"use_all_factor_levels": bool(d.use_all_factor_levels),
            "standardize": bool(d.standardize),
            "linkname": str(model.linkname),
            "link_power": float(model.link_power),
            "nclasses": int(model._output.nclasses),
            "n_cat": len(d.cat_names),
            "n_num": len(d.num_names),
            "cards": [int(c) for c in d.cards]}


def glm_checksum(model) -> str:
    """Content hash of everything that shapes the fused GLM program
    (packed arrays + static meta) — same discipline as
    packer.model_checksum for forests."""
    h = hashlib.sha256()
    arrays = pack_glm(model)
    for name in sorted(arrays):
        a = np.ascontiguousarray(arrays[name])
        h.update(name.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    h.update(json.dumps(glm_meta(model), sort_keys=True).encode())
    return h.hexdigest()


def lower_glm_bucket(bucket: int, model):
    """Lowered (not yet compiled) GLM scoring program for one row bucket.

    This lowers ``models/glm._glm_predict`` ITSELF — the exact jit
    program in-process serving runs (eta from codes and coefficients + linkinv,
    with the DataInfo moments closed over as program constants) — so the
    artifact's outputs are bitwise-identical to ``GLMModel.predict`` by
    construction, not by re-implementation (the program is batch-size
    stable, so any bucket matches any padded in-process row count).
    Canonical per-column input dtypes: int32 categorical codes
    (``astype(int32)`` makes the narrow in-frame dtypes equivalent),
    float32 numerics; the runner packs to the same."""
    import jax

    from h2o3_tpu.models.glm import _glm_predict

    d = model.dinfo
    K = int(model._output.nclasses)
    structs = tuple(jax.ShapeDtypeStruct((int(bucket),), np.int32)
                    for _ in d.cat_names) + \
        tuple(jax.ShapeDtypeStruct((int(bucket),), np.float32)
              for _ in d.num_names)
    beta_s = jax.ShapeDtypeStruct(np.asarray(model.beta).shape, np.float32)
    # offset rides as the same concrete 0.0 scalar _predict_raw passes
    return _glm_predict.lower(structs, beta_s, 0.0, dinfo=d,
                              linkname=model.linkname,
                              link_power=model.link_power,
                              nclasses=K if K > 2 else 1)


def compile_glm_bucket(bucket: int, model
                       ) -> Tuple[Any, bytes, str, Any]:
    """AOT-compile the GLM program for one row bucket; returns
    (compiled, blob, stablehlo_text, kept_arg_indices_or_None) —
    the GLM twin of aot.compile_bucket, ledger family "artifact"."""
    from h2o3_tpu.artifact import aot
    from h2o3_tpu.obs import compiles

    d = model.dinfo
    lowered = lower_glm_bucket(bucket, model)
    text = lowered.as_text()
    compiled = compiles.compile_lowered(
        "artifact", lowered,
        signature=("artifact_glm", int(bucket),
                   int(model._output.nclasses), str(model.linkname)),
        program=f"artifact_glm_bucket_{int(bucket)}")
    nargs = len(d.cat_names) + len(d.num_names) + 2   # cols + beta + offset
    return (compiled, aot.serialize_exec_blob(compiled), text,
            aot.kept_arg_indices(compiled, text, nargs))

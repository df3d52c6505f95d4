"""AOT lowering/compilation of the fused scoring program.

Reference grounding: "Automatic Full Compilation of Julia Programs and ML
Models to Cloud TPUs" (PAPERS.md) — ship the *compiled program*, not the
model interpreter. Per (model, bucket) the exporter lowers the fused
bin+traverse+init program once with ``jax.jit(...).lower(...).compile()``
and serializes the executable (``jax.experimental.serialize_executable``
via compat.py); the StableHLO text of the same lowering rides along as the
portable fallback for targets whose backend cannot deserialize the binary.

Artifact executables are deliberately lowered SINGLE-DEVICE (no mesh
sharding): the standalone serving tier is one process per replica, and a
single-device program loads on any topology. The in-server compile cache
(compile_cache.py) snapshots mesh-sharded executables instead — its
fingerprint covers the mesh, so the two never mix.
"""

from __future__ import annotations

import io
import pickle
from typing import Any, Dict, Tuple

import numpy as np

# 2: the forest programs take the level-ordered view (compressed.WALK_ARGS,
# ISSUE 33); an executable serialized before that has other inputs and is a
# cache miss, never a call with the wrong arguments
BLOB_VERSION = 2


def backend_fingerprint(single_device: bool = False) -> str:
    """String identity of the XLA target an executable was compiled for.
    Cache keys and artifact entries carry it; a mismatch means 'recompile
    here', never 'try to load anyway'."""
    import jax

    d = jax.devices()[0]
    parts = [
        "jax=" + jax.__version__,
        "platform=" + str(d.platform),
        "kind=" + str(getattr(d, "device_kind", "?")),
    ]
    if single_device:
        parts.append("devices=1")
    else:
        parts += [f"devices={jax.device_count()}",
                  f"processes={jax.process_count()}"]
    return ";".join(parts)


def fused_fn(max_depth: int, nclasses: int, per_class: bool):
    """The one fused scoring program (models/tree/compressed.py) — single
    source of truth for both in-process serving and artifact export."""
    from h2o3_tpu.models.tree.compressed import _fused_score_fn

    return _fused_score_fn(max_depth, nclasses, per_class)


def _arg_structs(bucket: int, edges: np.ndarray, is_cat: np.ndarray,
                 init: np.ndarray, forest_args: tuple):
    """ShapeDtypeStructs for one bucket's lowering (no shardings — the
    artifact program targets a single device)."""
    import jax
    import jax.numpy as jnp

    def s(a):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    F = int(is_cat.shape[0])
    return (jax.ShapeDtypeStruct((int(bucket), F), jnp.float32), s(edges),
            s(is_cat), s(init)) + tuple(s(a) for a in forest_args)


def lower_bucket(bucket: int, meta: Dict[str, Any], edges, is_cat, init,
                 forest_args):
    """Lowered (not yet compiled) fused program for one row bucket."""
    fn = fused_fn(int(meta["max_depth"]), int(meta["nclasses"]),
                  bool(meta["per_class_trees"]))
    return fn.lower(*_arg_structs(bucket, edges, is_cat, init, forest_args))


def serialize_exec_blob(compiled) -> bytes:
    """Executable -> self-contained blob: a pickle of
    ``{v, payload, in_tree, out_tree, devices}`` — loaded ONLY through
    :func:`load_exec_blob`'s restricted unpickler. ``devices`` are the ids
    the program was compiled for, so a mesh-wide serving executable loads
    back over the same mesh and a single-device one over one device.
    Raises when the backend cannot serialize the executable."""
    from h2o3_tpu import compat

    payload, in_tree, out_tree = compat.serialize_compiled(compiled)
    return pickle.dumps({"v": BLOB_VERSION, "payload": payload,
                         "in_tree": in_tree, "out_tree": out_tree,
                         "devices": compat.compiled_device_ids(compiled)},
                        protocol=pickle.HIGHEST_PROTOCOL)


class _ExecBlobUnpickler(pickle.Unpickler):
    """Executable blobs hold bytes + jax PyTreeDefs and nothing else; any
    other global reference is an attack, not a format evolution."""

    _PREFIXES = ("jax.", "jaxlib.", "numpy.")
    _MODULES = {"jax", "jaxlib", "numpy"}

    def find_class(self, module, name):
        if module in self._MODULES or \
                any(module.startswith(p) for p in self._PREFIXES):
            return super().find_class(module, name)
        raise pickle.UnpicklingError(
            f"executable blob references disallowed type {module}.{name}")


def load_exec_blob(blob: bytes):
    """Blob -> callable loaded executable. Raises on version mismatch,
    disallowed pickle globals, or a backend that cannot deserialize it —
    callers treat every raise as a cache/fallback miss."""
    from h2o3_tpu import compat

    d = _ExecBlobUnpickler(io.BytesIO(blob)).load()
    if not isinstance(d, dict) or d.get("v") != BLOB_VERSION:
        raise ValueError(f"unsupported executable blob version "
                         f"{d.get('v') if isinstance(d, dict) else '?'}")
    return compat.deserialize_compiled(d["payload"], d["in_tree"],
                                       d["out_tree"],
                                       device_ids=d.get("devices"))


def kept_arg_indices(compiled, text: str, nargs: int):
    """Indices of the Python-level args the lowered program actually takes.
    jit prunes unused args from the XLA signature (e.g. tree_class when
    K == 1); the serialized-executable path carries that mapping itself,
    but the raw StableHLO fallback executes the MLIR main directly and
    must filter its argument list. Returns a sorted list, or None when the
    mapping cannot be established on this jax (the runner then skips the
    HLO fallback with a clear error instead of mis-binding buffers)."""
    import re

    kept = getattr(getattr(compiled, "_executable", None), "_kept_var_idx",
                   None)
    if kept:
        return sorted(int(i) for i in kept)
    m = re.search(r"@main\((.*?)\)\s*->", text, re.S)
    if m is not None and m.group(1).count("%arg") == nargs:
        return list(range(nargs))
    return None


def compile_bucket(bucket: int, meta: Dict[str, Any], edges, is_cat, init,
                   forest_args) -> Tuple[Any, bytes, str, Any]:
    """AOT-compile one bucket; returns (compiled, blob, stablehlo
    text, kept_arg_indices_or_None)."""
    from h2o3_tpu.obs import compiles

    lowered = lower_bucket(bucket, meta, edges, is_cat, init, forest_args)
    text = lowered.as_text()
    # ledger chokepoint (family "artifact"): the exporter's per-bucket
    # compile cost lands on /3/Runtime next to the serving compiles
    compiled = compiles.compile_lowered(
        "artifact", lowered,
        signature=("artifact", int(bucket), int(meta.get("max_depth", 0)),
                   int(meta.get("nclasses", 0))),
        program=f"artifact_bucket_{int(bucket)}")
    nargs = 4 + len(forest_args)
    return (compiled, serialize_exec_blob(compiled), text,
            kept_arg_indices(compiled, text, nargs))

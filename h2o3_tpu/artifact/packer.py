"""Packed-constant codecs: forest/spec arrays and tree-progress chunks.

One layout serves three consumers:

- the artifact exporter packs a trained forest + its BinSpec into ONE
  ``forest.npz`` (``allow_pickle=False`` end to end — arrays are the whole
  payload, nothing executable);
- the standalone runner (h2o3_genmodel.aot) re-hydrates the scoring inputs
  from that npz with numpy alone;
- the durable-job-progress store appends per-tree training state as
  incremental *chunk* files of the same npz discipline, so a tree
  checkpoint writes only the trees grown since the previous save instead
  of re-serializing the whole forest (the recorded PR-5 O(forest) cost).
"""

from __future__ import annotations

import hashlib
import io
import json
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from h2o3_genmodel.levels import WALK_ARGS, level_view

# ---------------------------------------------------------------------------
# forest + spec <-> npz
# ---------------------------------------------------------------------------

def pack_forest(forest, spec) -> Dict[str, np.ndarray]:
    """Dense arrays for a CompressedForest + BinSpec (the MOJO payload
    layout, kept field-compatible with models/mojo.py so the two portable
    formats never drift)."""
    arrays = {
        "feat": np.asarray(forest.feat, np.int32),
        "thresh_bin": np.asarray(forest.thresh_bin, np.int32),
        "na_left": np.asarray(forest.na_left).astype(np.int8),
        "left": np.asarray(forest.left, np.int32),
        "right": np.asarray(forest.right, np.int32),
        "leaf_val": np.asarray(forest.leaf_val, np.float32),
        "cat_split": np.asarray(forest.cat_split, np.int32),
        "cat_table": np.asarray(forest.cat_table).astype(np.int8),
        "tree_class": np.asarray(forest.tree_class, np.int32),
        "na_bins": np.asarray(forest.na_bins, np.int32),
        "spec_nbins": np.asarray(spec.nbins, np.int64),
        "spec_is_cat": np.asarray(spec.is_cat).astype(np.int8),
        "spec_cards": np.asarray(spec.cards, np.int64),
        "spec_edges_flat": (np.concatenate(
            [np.asarray(e, np.float64) for e in spec.edges])
            if spec.edges else np.zeros(0)),
        "spec_edges_len": np.asarray([len(e) for e in spec.edges], np.int64),
    }
    if forest.init_class is not None:
        arrays["init_class"] = np.asarray(forest.init_class, np.float32)
    return arrays


def forest_meta(forest, spec) -> Dict[str, Any]:
    return {"max_depth": int(forest.max_depth),
            "init_f": float(forest.init_f),
            "nclasses": int(forest.nclasses),
            "per_class_trees": bool(forest.per_class_trees),
            "n_trees": int(forest.n_trees),
            "spec_names": list(spec.names)}


def dump_npz(arrays: Dict[str, np.ndarray]) -> bytes:
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def load_npz(data: bytes) -> Dict[str, np.ndarray]:
    with np.load(io.BytesIO(data), allow_pickle=False) as z:
        return {k: np.asarray(z[k]) for k in z.files}


def model_checksum(forest, spec) -> str:
    """Content hash of everything that shapes the fused scoring program:
    the packed arrays plus the scalar forest meta. The persistent compile
    cache and the artifact manifest both key on it, so a retrained model
    under the same DKV key can never be served a stale executable."""
    h = hashlib.sha256()
    arrays = pack_forest(forest, spec)
    for name in sorted(arrays):
        a = np.ascontiguousarray(arrays[name])
        h.update(name.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    h.update(json.dumps(forest_meta(forest, spec), sort_keys=True).encode())
    return h.hexdigest()


def padded_edges(edges_flat: np.ndarray, edges_len: np.ndarray,
                 F: int) -> np.ndarray:
    """(F, emax) float32 +inf-padded edge matrix — the exact construction
    ScoringSession.__init__ feeds the fused program, so binning in the
    standalone runner is bitwise-identical to in-process serving."""
    lens = [int(v) for v in np.asarray(edges_len).reshape(-1)]
    emax = max(lens, default=0) or 1
    ep = np.full((F, emax), np.inf, np.float32)
    pos = 0
    for i, ln in enumerate(lens):
        ep[i, :ln] = np.asarray(edges_flat[pos: pos + ln], np.float32)
        pos += ln
    return ep


def scoring_inputs(arrays: Dict[str, np.ndarray], max_depth: int
                   ) -> Tuple[np.ndarray, np.ndarray, tuple]:
    """(edges_padded, is_cat, forest_arg_tuple) in the fused program's
    argument order (compressed.WALK_ARGS: the level-ordered view, rebuilt
    from the stored arrays) — shared by the server-side loader and the
    standalone runner. A forest manifest records WALK_ARGS as `forest_args`:
    the layout the artifact's programs were lowered for."""
    F = int(arrays["spec_is_cat"].shape[0])
    edges = padded_edges(arrays["spec_edges_flat"], arrays["spec_edges_len"],
                         F)
    is_cat = arrays["spec_is_cat"].astype(bool)
    return edges, is_cat, level_view(arrays, max_depth)


# ---------------------------------------------------------------------------
# tree-progress chunks (append-only job-progress suffix files)
# ---------------------------------------------------------------------------

def pack_tree_chunk(packs: Sequence[np.ndarray],
                    leaf_vals: Sequence[np.ndarray],
                    leaf_wys: Sequence[np.ndarray]) -> bytes:
    """One suffix chunk = the per-tree tables for a contiguous run of
    newly-grown trees, stacked (every tree of a run shares its shapes) and
    npz-encoded. ``n`` rides along so a reader can sanity-check the stack."""
    n = len(packs)
    if not (n == len(leaf_vals) == len(leaf_wys)):
        raise ValueError("tree chunk lists disagree in length")
    return dump_npz({
        "n": np.asarray([n], np.int64),
        "packs": np.stack([np.asarray(p) for p in packs]),
        "leaf_vals": np.stack([np.asarray(v, np.float32)
                               for v in leaf_vals]),
        "leaf_wys": np.stack([np.asarray(w, np.float32) for w in leaf_wys]),
    })


def unpack_tree_chunk(data: bytes
                      ) -> Tuple[List[np.ndarray], List[np.ndarray],
                                 List[np.ndarray]]:
    arrays = load_npz(data)
    n = int(arrays["n"][0])
    if any(arrays[k].shape[0] != n for k in ("packs", "leaf_vals",
                                             "leaf_wys")):
        raise ValueError("torn tree chunk: stack lengths disagree with n")
    return ([arrays["packs"][i] for i in range(n)],
            [arrays["leaf_vals"][i] for i in range(n)],
            [arrays["leaf_wys"][i] for i in range(n)])

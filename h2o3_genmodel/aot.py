"""Standalone AOT-artifact scoring runtime (the genmodel side).

Loads an artifact directory exported by ``h2o3_tpu.artifact`` and scores
CSV / column input **without importing the training stack**: the only
dependencies are numpy, the standard library, and jax (to execute the
shipped program). Mirrors the MOJO runtime's charter (reader.py/easy.py)
for the AOT lineage.

Scoring path, in fallback order per row bucket:

1. deserialize the shipped AOT executable (``exec_b{N}.bin``) when its
   backend fingerprint matches this process — zero compilation, the
   cold-start-optimal path;
2. compile the shipped StableHLO text (``hlo_b{N}.mlir``) through the
   local XLA client — one compile of the *identical* program the exporter
   lowered, so predictions stay bitwise-identical to in-process serving.

Executable blobs pass through a restricted unpickler (bytes + jax
PyTreeDefs only) and every payload file is sha256-gated by the manifest
before any of its bytes are interpreted.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
from typing import Any, Dict, List, Optional

import numpy as np

from h2o3_genmodel.levels import WALK_ARGS, level_view

_FORMAT = "h2o3-tpu-aot-artifact"
_FORMAT_VERSION = 1
_BLOB_VERSION = 2      # h2o3_tpu.artifact.aot.BLOB_VERSION


class ArtifactError(ValueError):
    """Malformed / tampered / incompatible artifact."""


# ---------------------------------------------------------------------------
# manifest + payload reading (standalone twin of h2o3_tpu.artifact.manifest;
# tests/test_consistency.py pins the two formats together)
# ---------------------------------------------------------------------------

def _read_manifest(art_dir: str) -> Dict[str, Any]:
    path = os.path.join(art_dir, "manifest.json")
    try:
        with open(path, encoding="utf-8") as f:
            m = json.load(f)
    except (OSError, ValueError) as e:
        raise ArtifactError(f"no readable manifest in {art_dir!r}: {e}") \
            from None
    if not isinstance(m, dict) or m.get("format") != _FORMAT:
        raise ArtifactError(f"not an {_FORMAT} artifact")
    ver = m.get("format_version")
    if not isinstance(ver, int) or not 1 <= ver <= _FORMAT_VERSION:
        raise ArtifactError(
            f"artifact format_version {ver!r} unsupported by this runtime "
            f"(supports 1..{_FORMAT_VERSION})")
    for key in ("model_category", "names", "files", "buckets", "post",
                "max_depth", "nclasses", "init_f", "model_checksum"):
        if key not in m:
            raise ArtifactError(f"manifest missing required key {key!r}")
    return m


def _read_payload(art_dir: str, entry: Dict[str, Any]) -> bytes:
    name = str(entry.get("name") or "")
    if not name or os.path.basename(name) != name or name.startswith("."):
        raise ArtifactError(f"illegal payload file name {name!r}")
    try:
        with open(os.path.join(art_dir, name), "rb") as f:
            data = f.read()
    except OSError as e:
        raise ArtifactError(f"payload {name!r} unreadable: {e}") from None
    if hashlib.sha256(data).hexdigest() != entry.get("sha256"):
        raise ArtifactError(f"payload {name!r} checksum mismatch — "
                            "artifact is corrupt or was tampered with")
    return data


class _ExecBlobUnpickler(pickle.Unpickler):
    _PREFIXES = ("jax.", "jaxlib.", "numpy.")
    _MODULES = {"jax", "jaxlib", "numpy"}

    def find_class(self, module, name):
        if module in self._MODULES or \
                any(module.startswith(p) for p in self._PREFIXES):
            return super().find_class(module, name)
        raise pickle.UnpicklingError(
            f"executable blob references disallowed type {module}.{name}")


def _backend_fingerprint() -> str:
    import jax

    d = jax.devices()[0]
    return ";".join(["jax=" + jax.__version__,
                     "platform=" + str(d.platform),
                     "kind=" + str(getattr(d, "device_kind", "?")),
                     "devices=1"])


# ---------------------------------------------------------------------------
# the scorer
# ---------------------------------------------------------------------------

class AotScorer:
    """One loaded artifact: packed constants on device + one executable
    per row bucket, resolved lazily (deserialize -> StableHLO compile)."""

    def __init__(self, art_dir: str):
        self.dir = str(art_dir)
        m = _read_manifest(self.dir)
        self.manifest = m
        self.model_type: str = str(m.get("model_type") or "forest")
        if self.model_type not in ("forest", "glm", "pipeline"):
            raise ArtifactError(f"unsupported artifact model_type "
                                f"{self.model_type!r}")
        self.names: List[str] = list(m["names"])
        self.category: str = str(m["model_category"])
        self.response_domain: List[str] = list(m.get("response_domain")
                                               or [])
        self.default_threshold = float(m.get("default_threshold", 0.5))
        self.post: Dict[str, Any] = dict(m["post"])
        self.buckets: List[int] = sorted(int(b) for b in m["buckets"])
        self.nclasses = int(m["nclasses"])
        self.per_class = bool(m.get("per_class_trees"))

        if self.model_type == "pipeline":
            # the munge→score program ships with every constant (feature
            # plan consts + model tables) baked in; the manifest's
            # `pipeline` block and plan payload are the human-readable
            # record of WHAT was fused, verified here but not interpreted
            p = m.get("pipeline")
            if not isinstance(p, dict):
                raise ArtifactError("pipeline artifact manifest missing "
                                    "its 'pipeline' block")
            self.pipeline: Dict[str, Any] = dict(p)
            if "pipeline" not in m["files"]:
                raise ArtifactError("pipeline artifact manifest names no "
                                    "'pipeline' payload file")
            _read_payload(self.dir, m["files"]["pipeline"])
            self._arrays: Dict[str, np.ndarray] = {}
            self.domains: Dict[str, List[str]] = {
                k: list(v) for k, v in (m.get("domains") or {}).items()}
            self._dev: Optional[tuple] = None
            self._exec: Dict[int, Any] = {}
            self._post_jit = None
            self.loaded_from: Dict[int, str] = {}
            return
        payload = m["files"]["glm" if self.model_type == "glm"
                             else "forest"]
        with np.load(io.BytesIO(_read_payload(self.dir, payload)),
                     allow_pickle=False) as z:
            arrays = {k: np.asarray(z[k]) for k in z.files}
        self._arrays = arrays
        F = len(self.names)
        if self.model_type == "glm":
            g = m.get("glm")
            if not isinstance(g, dict):
                raise ArtifactError("glm artifact manifest missing its "
                                    "'glm' configuration block")
            self.glm: Dict[str, Any] = dict(g)
            if int(g.get("n_cat", 0)) + int(g.get("n_num", 0)) != F:
                raise ArtifactError("glm layout disagrees with manifest "
                                    "names")
        else:
            if int(arrays["spec_is_cat"].shape[0]) != F:
                raise ArtifactError("packed spec width disagrees with "
                                    "manifest names")
            # both load paths (serialized executable, StableHLO) bind the
            # forest inputs by position: refuse programs lowered for
            # another layout than _device_args builds
            if m.get("forest_args") != list(WALK_ARGS):
                raise ArtifactError(
                    f"the artifact's programs take the forest as "
                    f"{m.get('forest_args') or 'the stored arrays'}, this "
                    f"runtime passes {list(WALK_ARGS)} — re-export the "
                    "artifact on a current framework build")
            self.is_cat = arrays["spec_is_cat"].astype(bool)
        self.domains: Dict[str, List[str]] = {
            k: list(v) for k, v in (m.get("domains") or {}).items()}
        # device-side constants are materialized on first use (load() stays
        # import-cheap for cold-start measurement)
        self._dev: Optional[tuple] = None
        self._exec: Dict[int, Any] = {}
        self._post_jit = None                     # cached fused post program
        self.loaded_from: Dict[int, str] = {}     # bucket -> "exec"|"hlo"

    # -- device constants -------------------------------------------------
    def _device_args(self) -> tuple:
        if self._dev is not None:
            return self._dev
        import jax.numpy as jnp

        a = self._arrays
        if self.model_type == "pipeline":
            self._dev = ()           # everything is baked into the program
            return self._dev
        if self.model_type == "glm":
            # the GLM program bakes the DataInfo moments in as constants;
            # only beta (and the offset scalar) ride as arguments
            self._dev = (jnp.asarray(a["beta"].astype(np.float32)),)
            return self._dev
        F = len(self.names)
        lens = [int(v) for v in a["spec_edges_len"].reshape(-1)]
        emax = max(lens, default=0) or 1
        ep = np.full((F, emax), np.inf, np.float32)
        flat, pos = a["spec_edges_flat"], 0
        for i, ln in enumerate(lens):
            ep[i, :ln] = np.asarray(flat[pos: pos + ln], np.float32)
            pos += ln
        init = (np.asarray(a["init_class"], np.float32)
                if "init_class" in a
                else np.float32(self.manifest["init_f"]))
        # the program's forest inputs are the level-ordered view of the
        # stored arrays, rebuilt here as the exporter built it
        self._dev = tuple(jnp.asarray(x) for x in (
            ep, self.is_cat, init,
            *level_view(a, int(self.manifest["max_depth"]))))
        return self._dev

    # -- executables ------------------------------------------------------
    def _executable(self, bucket: int):
        exe = self._exec.get(bucket)
        if exe is not None:
            return exe
        m = self.manifest
        fp = _backend_fingerprint()
        for e in m.get("executables", []):
            if int(e.get("bucket", -1)) != bucket or e.get("backend") != fp:
                continue
            blob = _read_payload(self.dir, e)
            try:
                d = _ExecBlobUnpickler(io.BytesIO(blob)).load()
                if not isinstance(d, dict) or d.get("v") != _BLOB_VERSION:
                    raise ArtifactError("unsupported executable blob "
                                        "version")
                import jax
                from jax.experimental import serialize_executable as se

                # artifact programs are single-device by contract: load on
                # one device, or the executable wants a shard per device
                loaded = se.deserialize_and_load(
                    d["payload"], d["in_tree"], d["out_tree"],
                    execution_devices=jax.devices()[:1])
            except pickle.UnpicklingError:
                raise            # tampered blob: refuse, never fall back
            except Exception:    # noqa: BLE001 — backend can't load: HLO
                break
            self._exec[bucket] = ("loaded", loaded)
            self.loaded_from[bucket] = "exec"
            return self._exec[bucket]
        for e in m.get("stablehlo", []):
            if int(e.get("bucket", -1)) != bucket:
                continue
            kept = e.get("kept_args")
            if kept is None:
                raise ArtifactError(
                    f"bucket {bucket}: no loadable executable for this "
                    "backend and the StableHLO entry carries no argument "
                    "mapping — re-export the artifact on a current "
                    "framework build")
            import jax
            from jax.extend import backend as jex_backend
            from jaxlib import xla_client as xc

            text = _read_payload(self.dir, e).decode("utf-8")
            dev = jax.devices()[0]
            raw = dev.client.compile_and_load(
                text, xc.DeviceList((dev,)),
                jex_backend.get_compile_options(num_replicas=1,
                                                num_partitions=1))
            self._exec[bucket] = ("raw", raw, [int(i) for i in kept])
            self.loaded_from[bucket] = "hlo"
            return self._exec[bucket]
        raise ArtifactError(f"artifact has no program for bucket {bucket}")

    def _split_glm_cols(self, X_pad: np.ndarray) -> List[np.ndarray]:
        """(bucket, P) matrix → the per-column argument list the GLM
        program was lowered with: int32 categorical codes (NaN/negative →
        -1, which the program's mode imputation sees as NA — the same
        value adapt_test's unseen-level remap produces), then float32
        numerics."""
        ncat = int(self.glm["n_cat"])
        cols: List[np.ndarray] = []
        for i in range(ncat):
            c = X_pad[:, i]
            cols.append(np.where(np.isnan(c), -1.0, c).astype(np.int32))
        for j in range(int(self.glm["n_num"])):
            cols.append(np.ascontiguousarray(X_pad[:, ncat + j],
                                             np.float32))
        return cols

    def _run_dev(self, bucket: int, X_pad: np.ndarray):
        """Dispatch one bucket; returns the program output WITHOUT forcing
        a host transfer (the serving-QPS path keeps it device-resident
        through post-processing and fetches once)."""
        import jax.numpy as jnp

        got = self._executable(bucket)
        if self.model_type == "pipeline":
            # one program: raw (bucket, R) matrix in, margins/mu out.
            # The offset scalar rides as the second argument exactly like
            # the glm lowering (kept-args filtering prunes it for forest
            # cores).
            if got[0] == "loaded":
                return got[1](X_pad, 0.0)
            _kind, exe, kept = got
            flat = [jnp.asarray(X_pad), jnp.float32(0.0)]
            outs = exe.execute([flat[i] for i in kept])
            return outs[0]
        if self.model_type == "glm":
            cols = self._split_glm_cols(X_pad)
            (beta,) = self._device_args()
            if got[0] == "loaded":
                # the lowered pytree: (cols_tuple, beta, offset) — offset
                # is the same concrete 0.0 _predict_raw passes. Host
                # arrays go in as-is: the loaded executable's C++ call
                # path device-puts them faster than an explicit asarray.
                return got[1](tuple(cols), beta, 0.0)
            _kind, exe, kept = got
            flat = [jnp.asarray(c) for c in cols] + [beta,
                                                     jnp.float32(0.0)]
            outs = exe.execute([flat[i] for i in kept])
            return outs[0]
        if got[0] == "loaded":
            # numpy straight in — the executable's own transfer path is
            # measurably cheaper than jnp.asarray + call
            return got[1](X_pad, *self._device_args())
        args = (jnp.asarray(X_pad),) + self._device_args()
        _kind, exe, kept = got
        # jit pruned unused Python-level args from the XLA signature; the
        # raw-client execute path must bind only the kept ones, in order
        outs = exe.execute([args[i] for i in kept])
        return outs[0]

    def _run(self, bucket: int, X_pad: np.ndarray) -> np.ndarray:
        return np.asarray(self._run_dev(bucket, X_pad))

    # -- feature packing --------------------------------------------------
    def pack_features(self, cols: Dict[str, Any]) -> np.ndarray:
        """(n, F) float32 matrix in training-column order: numerics as
        floats (unparseable/missing -> NaN), categoricals as training-
        domain codes (unseen/missing -> -1, which bins to the NA bin) —
        the same convention ScoringSession._features feeds the program."""
        n = 0
        for v in cols.values():
            n = max(n, len(np.asarray(v, dtype=object).reshape(-1)))
        X = np.empty((n, len(self.names)), np.float32)
        for i, name in enumerate(self.names):
            dom = self.domains.get(name)
            raw = cols.get(name)
            if raw is None:
                X[:, i] = -1.0 if dom is not None else np.nan
                continue
            vals = np.asarray(raw, dtype=object).reshape(-1)
            if dom is not None:
                lut = {str(lvl): k for k, lvl in enumerate(dom)}
                X[:, i] = [lut.get(str(v).strip(), -1)
                           if v is not None and str(v).strip() != ""
                           else -1 for v in vals]
            else:
                def as_float(v):
                    try:
                        return float(v)
                    except (TypeError, ValueError):
                        return np.nan
                X[:, i] = [as_float(v) for v in vals]
        return X

    # -- scoring ----------------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def margins(self, X: np.ndarray) -> np.ndarray:
        """(n,) or (n, K) float32 margins — bitwise-identical to the
        server's fused bucketed program (it IS the server's program)."""
        X = np.asarray(X, np.float32)
        n = X.shape[0]
        maxb = self.buckets[-1]
        outs: List[np.ndarray] = []
        pos = 0
        while pos < n:
            chunk = X[pos: pos + maxb]
            m = chunk.shape[0]
            bucket = self._bucket_for(m)
            buf = np.zeros((bucket, X.shape[1]), np.float32)
            buf[:m] = chunk
            outs.append(self._run(bucket, buf)[:m])
            pos += m
        if not outs:
            K = (self.nclasses
                 if (self.nclasses > 2 or self.per_class) else 1)
            return np.zeros((0,) if K == 1 else (0, K), np.float32)
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    def _out_key(self) -> str:
        return "probs" if self.post.get("kind") in (
            "binomial", "multinomial", "glm_binomial",
            "glm_multinomial") else "value"

    def _post(self, f_dev):
        """Post-processing (margins → probs/value) as ONE cached jit
        program over the device-resident margins — the identical jnp ops
        the server runs in _margin_to_raw, fused so a request pays a
        single extra dispatch instead of one per eager op."""
        fn = self._post_jit
        if fn is None:
            import jax
            import jax.numpy as jnp

            kind = self.post.get("kind")
            exp_link = self.post.get("linkinv") == "exp"
            if kind in ("binomial", "glm_binomial"):
                def post(f):
                    p = 1.0 / (1.0 + jnp.exp(-f)) if kind == "binomial" \
                        else f        # glm program already applied linkinv
                    return jnp.stack([1 - p, p], axis=-1)
            elif kind == "multinomial":
                def post(f):
                    return jax.nn.softmax(f, axis=-1)
            elif kind == "glm_multinomial":
                def post(f):          # probs computed inside the program
                    return f
            elif exp_link:
                def post(f):
                    return jnp.exp(f)
            else:
                def post(f):
                    return f

            fn = self._post_jit = jax.jit(post)
        return fn(f_dev)

    def raw_predict(self, X: np.ndarray) -> Dict[str, np.ndarray]:
        """Margins + post-processing with the identical jnp ops the server
        runs in SharedTreeModel._margin_to_raw / GLM's linkinv — computed
        as one device-resident pipeline per bucket chunk (program dispatch
        → fused post program → ONE host fetch). This is the sustained-QPS
        path: no intermediate host round-trip, no per-eager-op dispatch,
        and an exactly-bucket-sized batch skips the pad copy."""
        X = np.asarray(X, np.float32)
        n = X.shape[0]
        maxb = self.buckets[-1]
        outs: List[np.ndarray] = []
        pos = 0
        while pos < n:
            chunk = X[pos: pos + maxb]
            m = chunk.shape[0]
            bucket = self._bucket_for(m)
            if m == bucket:
                buf = np.ascontiguousarray(chunk, np.float32)
            else:
                buf = np.zeros((bucket, X.shape[1]), np.float32)
                buf[:m] = chunk
            out = self._post(self._run_dev(bucket, buf))
            outs.append(np.asarray(out)[:m])
            pos += m
        if not outs:
            K = (self.nclasses
                 if (self.nclasses > 2 or self.per_class) else 1)
            if self._out_key() == "probs":
                width = self.nclasses if self.nclasses > 2 else 2
                return {"probs": np.zeros((0, width), np.float32)}
            return {"value": np.zeros((0,) if K == 1 else (0, K),
                                      np.float32)}
        res = outs[0] if len(outs) == 1 else np.concatenate(outs)
        return {self._out_key(): res}

    def raw_from_margins(self, margins: np.ndarray
                         ) -> Dict[str, np.ndarray]:
        import jax.numpy as jnp

        return {self._out_key():
                np.asarray(self._post(jnp.asarray(margins)))}

    def score(self, cols: Dict[str, Any],
              raw: Dict[str, np.ndarray] = None) -> Dict[str, np.ndarray]:
        """Batch scoring: raw columns -> the server predict-frame shape
        (predict + per-class probability columns). Pass `raw` to label a
        result already computed via raw_predict/raw_from_margins instead
        of scoring the columns again."""
        if raw is None:
            raw = self.raw_predict(self.pack_features(cols))
        out: Dict[str, np.ndarray] = {}
        if "probs" in raw:
            probs = np.asarray(raw["probs"])
            dom = self.response_domain or [str(i)
                                           for i in range(probs.shape[1])]
            if self.category == "Binomial":
                label = (probs[:, 1] >= self.default_threshold).astype(int)
            else:
                label = probs.argmax(axis=-1)
            out["predict"] = np.asarray([dom[i] for i in label], object)
            for k, lvl in enumerate(dom):
                out[str(lvl)] = probs[:, k]
        else:
            out["predict"] = np.asarray(raw["value"])
        return out


def load_artifact(art_dir: str) -> AotScorer:
    """Load an AOT artifact directory into a standalone scorer."""
    return AotScorer(art_dir)

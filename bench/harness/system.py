"""The one module of the benchmark that touches the program.

It boots the system under test the way a user does (``h2o3_tpu.init()`` and
``h2o3_tpu.start_server(port=0)``), installs the benchmark's own arrays as
frames, and reads back what the program produced: a trained model's trees or
coefficients, a predictions frame, its spans and counters. Everything else in
``bench/`` works on plain arrays and dicts.
"""

from __future__ import annotations

import time

import numpy as np

from bench.harness import data as recipe

class System:
    """A booted cloud with its REST server, and the device it runs on."""

    READERS = {"gbm": "read_forest", "glm": "read_glm"}

    def __init__(self, chips: int, dry_run: bool):
        t0 = time.perf_counter()
        import jax

        import h2o3_tpu

        self.jax = jax
        self.h2o = h2o3_tpu
        self.cluster = h2o3_tpu.init()
        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        self.dry_run = bool(dry_run)
        if devs[0].platform != "tpu" and not dry_run:
            raise SystemExit(
                f"bench: JAX booted platform {devs[0].platform!r}, not a TPU;"
                f" nothing was run (--cpu-dry-run rehearses without a chip)")
        if len(devs) != int(chips) and not (dry_run and
                                            devs[0].platform != "tpu"):
            raise SystemExit(
                f"bench: the cell asks for {chips} chip(s), JAX found "
                f"{len(devs)}; nothing was run")
        self.server = h2o3_tpu.start_server(port=0)
        self.port = self.server.port
        self.boot_s = time.perf_counter() - t0

    # -- frames -------------------------------------------------------------
    def row_sharding(self):
        return self.cluster.row_sharding()

    def _check_rows(self, n: int):
        if self.cluster.pad_rows(n) != n:
            raise ValueError(f"{n} rows do not tile the mesh; use a multiple "
                             f"of {self.cluster.pad_rows(1)}")

    def install_training_frame(self, key: str, cols, y) -> None:
        """Device columns (already row-sharded) -> a resident frame with the
        28 features and the enum response."""
        from h2o3_tpu.core.frame import Column, code_dtype

        n = int(y.shape[0])
        self._check_rows(n)
        fr = self.h2o.H2OFrame(destination_frame=key)
        for name, c in zip(recipe.FEATURE_NAMES, cols):
            fr.add(name, Column.from_device(c, "real", n))
        codes = y.astype(code_dtype(len(recipe.RESPONSE_DOMAIN)))
        fr.add(recipe.RESPONSE_NAME,
               Column.from_device(codes, "enum", n,
                                  domain=list(recipe.RESPONSE_DOMAIN)))
        fr.install()

    def install_feature_frame(self, key: str, cols) -> None:
        """A scoring frame: the 28 features, no response. ``cols`` are device
        columns that tile the mesh, or host arrays of any length."""
        from h2o3_tpu.core.frame import Column

        fr = self.h2o.H2OFrame(destination_frame=key)
        for name, c in zip(recipe.FEATURE_NAMES, cols):
            if isinstance(c, np.ndarray):
                fr.add(name, Column.from_numpy(c))
            else:
                self._check_rows(int(c.shape[0]))
                fr.add(name, Column.from_device(c, "real", int(c.shape[0])))
        fr.install()

    # -- what the program produced -------------------------------------------
    def model(self, model_id: str):
        from h2o3_tpu.core.dkv import DKV

        m = DKV.get(model_id)
        if m is None:
            raise KeyError(f"no model {model_id!r} in the store")
        return m

    def read_forest(self, model_id: str) -> dict:
        """The trained trees as plain arrays: split feature, real-valued
        threshold (x <= thr goes left), children, leaf value and row count
        per node; the prior margin; the bin edges."""
        m = self.model(model_id)
        fo, spec = m.forest, m.spec
        feat = np.asarray(fo.feat, np.int32)
        tb = np.asarray(fo.thresh_bin, np.int64)
        thr = np.zeros(feat.shape, np.float32)
        for t, nid in zip(*np.nonzero(feat >= 0)):
            thr[t, nid] = spec.threshold_value(int(feat[t, nid]),
                                               int(tb[t, nid]))
        return {"feat": feat, "thr": thr,
                "left": np.asarray(fo.left, np.int32),
                "right": np.asarray(fo.right, np.int32),
                "leaf": np.asarray(fo.leaf_val, np.float32),
                "cover": np.asarray(fo.cover, np.float64),
                "init_f": float(fo.init_f),
                "edges": [np.asarray(e, np.float32) for e in spec.edges],
                "max_depth": int(fo.max_depth)}

    def read_glm(self, model_id: str) -> dict:
        """Coefficients on the original scale (intercept under 'Intercept')
        and the iterations IRLS took."""
        m = self.model(model_id)
        return {"coef": {k: float(v) for k, v in m.coef().items()},
                "iterations": int(getattr(m, "iterations", 0) or 0)}

    def read_model(self, algo: str, model_id: str) -> dict:
        return getattr(self, self.READERS[algo])(model_id)

    def builder_ms(self, model_id: str) -> float:
        return float(self.model(model_id)._output.run_time_ms)

    def read_column(self, frame_key: str, column: str):
        """One column of a resident frame as a device array of its rows."""
        from h2o3_tpu.core.dkv import DKV

        fr = DKV.get(frame_key)
        if fr is None:
            raise KeyError(f"no frame {frame_key!r} in the store")
        return fr.col(column).data[: fr.nrows]

    # -- spans and counters ----------------------------------------------------
    def spans(self, root: str = "ingress") -> list:
        """Finished spans of the traces the store still holds, one list per
        trace whose root span is ``root``."""
        from h2o3_tpu.obs import tracing

        out = []
        for rec in tracing.recent_traces(tracing.trace_cap()):
            if rec.get("root") == root:
                out.append(tracing.get_trace(rec["trace_id"],
                                             include_remote=False))
        return out

    # -- device -------------------------------------------------------------------
    def memory_peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.jax.devices()]
        return int(max(peaks))

    def free_program_state(self) -> None:
        """Drop every frame and model and the program's compiled state, so
        that the reference has the device to itself."""
        from h2o3_tpu import scoring
        from h2o3_tpu.core.dkv import DKV

        for k in list(DKV.keys()):
            try:
                scoring.purge(k)
            except Exception:       # noqa: BLE001 — not every key is a model
                pass
            DKV.remove(k)
        import gc

        gc.collect()

    def stop(self) -> None:
        self.server.stop()

"""Cluster runtime: mesh bootstrap, config, node info.

Replaces H2O-3's cloud-of-JVMs boot (reference: h2o-core/src/main/java/water/
H2O.java:1776 startLocalNode, :1811 startNetworkServices, water/Paxos.java:27
heartbeat-gossip membership). TPU-native design: membership is the set of JAX
processes/devices — static per job, which matches H2O's locked-cloud
semantics (water/Paxos.java:144 lockCloud: no elastic join after first job).
There is no Paxos to run: `jax.distributed.initialize()` (multi-host) or the
local device list (single-host) IS the cloud.
"""

from __future__ import annotations

from h2o3_tpu.compat import shard_map as _compat_shard_map
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np


# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_JAX_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_jax_cache() -> None:
    """JAX's persistent compile cache, placed from outside: where
    JAX_COMPILATION_CACHE_DIR says when it is set (then nothing is set in
    code), else one fixed directory in the checkout. The path is how a
    later process finds the cache again, so it never holds a temp dir, a
    pid or a timestamp. Called once, before the boot's first compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_JAX_CACHE)


@dataclass
class OptArgs:
    """Config/flag system (reference: water/H2O.java:316 OptArgs).

    Values may be overridden by environment variables H2O_TPU_<NAME>,
    mirroring H2O's -Dai.h2o.X=Y system-property pass-through
    (water/H2O.java:321 SYSTEM_PROP_PREFIX)."""

    name: str = "h2o3-tpu"
    # mesh shape: rows axis = data parallel over devices; model axis for TP.
    mesh_shape: Optional[Sequence[int]] = None
    mesh_axes: Sequence[str] = ("rows", "model")
    # row shard padding multiple (static shapes: ESPC replaced by padding,
    # SURVEY.md §7 "ESPC ragged chunks -> equal shard sizes with tail padding")
    row_align: int = 8
    # device storage dtype for numeric columns: "float32" (default) or
    # "bfloat16" (halves HBM; ops upcast at their boundaries)
    numeric_dtype: str = "float32"
    log_level: str = "INFO"
    ice_root: str = field(default_factory=lambda: os.environ.get("H2O_TPU_ICE_ROOT", "/tmp/h2o3_tpu"))
    # multi-host
    coordinator_address: Optional[str] = None
    num_processes: int = 1
    process_id: int = 0
    # explicit device list (dryrun/test harnesses pin a subset or a forced
    # CPU mesh); None = all of jax.devices()
    devices: Optional[Sequence] = None

    @staticmethod
    def from_env() -> "OptArgs":
        args = OptArgs()
        for f in ("name", "log_level", "ice_root", "coordinator_address",
                  "numeric_dtype"):
            v = os.environ.get("H2O_TPU_" + f.upper())
            if v is not None:
                setattr(args, f, v)
        for f in ("num_processes", "process_id", "row_align"):
            v = os.environ.get("H2O_TPU_" + f.upper())
            if v is not None:
                setattr(args, f, int(v))
        return args


class Cluster:
    """The booted runtime: device mesh + per-node info.

    H2O parity: `GET /3/Cloud` surface (water/api/CloudHandler.java) maps to
    :meth:`info`; the boot-time hardware probes (water/init/Linpack.java,
    MemoryBandwidth.java) map to :meth:`self_benchmark`."""

    def __init__(self, args: OptArgs):
        import jax

        from h2o3_tpu.obs import phases

        self.args = args
        self.start_time = time.time()
        self._jax = jax
        # each boot step is its own deadline-supervised lifecycle phase
        # with timeline events, so a start-up that hangs names the step
        if args.coordinator_address and args.num_processes > 1:
            with phases.enter("cloud_form", processes=args.num_processes):
                jax.distributed.initialize(
                    coordinator_address=args.coordinator_address,
                    num_processes=args.num_processes,
                    process_id=args.process_id,
                )
        with phases.enter("backend_init",
                          platforms=os.environ.get("JAX_PLATFORMS", "")):
            # first XLA client touch. Whatever platform JAX booted is
            # the platform: nothing below switches to another one, and
            # callers that need a chip (chip_smoke.py) assert it
            platform = jax.default_backend()
        with phases.enter("device_discovery", platform=platform):
            self.devices = (list(args.devices) if args.devices
                            else jax.devices())
        n = len(self.devices)
        with phases.enter("mesh_init", devices=n):
            if args.mesh_shape is None:
                shape = (n, 1)
            else:
                shape = tuple(args.mesh_shape)
            dev_grid = np.array(self.devices).reshape(shape)
            self.mesh = jax.sharding.Mesh(
                dev_grid, tuple(args.mesh_axes[: dev_grid.ndim]))
            self.n_devices = n
            self.locked = False  # parity flag; membership is static here
            # multi-process clouds run the liveness beater (HeartBeatThread
            # analog) so /3/Cloud's process_health stays fresh
            self._heartbeat = None
            if jax.process_count() > 1:
                from h2o3_tpu.core.failure import HeartbeatThread

                self._heartbeat = HeartbeatThread(interval_s=5.0).start()
        place_jax_cache()
        from h2o3_tpu.obs import compiles

        compiles.watch_backend_compiles()
        with phases.enter("first_compile"):
            # the supervised tiny boot compile: separates "backend up but
            # the first compile hangs" from "backend init hangs"
            import jax.numpy as jnp

            exe = compiles.compile_jit(
                "probe", jax.jit(lambda x: x + jnp.float32(1)),
                (jax.ShapeDtypeStruct((), jnp.float32),),
                signature="boot_first_compile", program="boot_probe")
            exe(jnp.float32(0)).block_until_ready()

    # -- sharding helpers -------------------------------------------------
    def row_sharding(self):
        """NamedSharding placing axis 0 over the 'rows' mesh axis — the
        TPU-native replacement for chunk homing by Key hash
        (water/Key.java:88-107)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P("rows"))

    def replicated_sharding(self):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    @property
    def row_shards(self) -> int:
        return int(self.mesh.shape["rows"])

    def pad_rows(self, n: int) -> int:
        """Smallest padded length >= n divisible by (row_shards * row_align)."""
        m = self.row_shards * self.args.row_align
        return max(int(-(-n // m) * m), m)

    def put_rows(self, buf: np.ndarray):
        """Pin a padded host array into device memory row-sharded. In
        multi-process mode each process materializes only its addressable
        shards from its (replicated) host copy — the multi-host analog of
        H2O's parse-then-home-chunks ingestion (every node reads its share)."""
        import jax

        sh = self.row_sharding()
        if jax.process_count() > 1:
            return jax.make_array_from_callback(
                buf.shape, sh, lambda idx: buf[idx])
        return jax.device_put(buf, sh)

    def reshard_rows(self, x):
        """Re-lay an existing device array out over the rows axis. Eager
        device_put single-process; a compiled identity with out_shardings in
        multi-process mode (cross-host resharding must go through XLA)."""
        import jax

        sh = self.row_sharding()
        if jax.process_count() > 1:
            return jax.jit(lambda a: a, out_shardings=sh)(x)
        return jax.device_put(x, sh)

    # -- info / observability --------------------------------------------
    def info(self) -> dict:
        import jax

        from h2o3_tpu.core import failure
        from h2o3_tpu.parallel import distributed as D

        return {
            "cloud_name": self.args.name,
            "version": "h2o3_tpu",
            "cloud_size": self.n_devices,
            "cloud_uptime_millis": int((time.time() - self.start_time) * 1000),
            "cloud_healthy": True,
            "locked": self.locked,
            "platform": jax.default_backend(),
            # recovery-layer identity: which election epoch this cloud is
            # in, who leads it, and this process's incarnation (bumped by
            # every rejoin) — surfaced on /3/CloudStatus
            "epoch": D.epoch(),
            "leader": D.leader(),
            "incarnation": failure.incarnation(),
            "nodes": [
                {"name": str(d), "platform": d.platform, "id": d.id}
                for d in self.devices
            ],
        }

    def self_benchmark(self, size: int = 1024) -> dict:
        """Boot probes, the analogs of water/init/Linpack.java (matmul
        GFLOPs), water/init/MemoryBandwidth.java (HBM stream GB/s) and
        water/init/NetworkBench.java (collective latency over the mesh —
        ICI on real pods)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        x = jnp.ones((size, size), jnp.float32)
        f = jax.jit(lambda a: a @ a)
        f(x).block_until_ready()  # compile
        t0 = time.perf_counter()
        reps = 10
        y = x
        for _ in range(reps):
            y = f(y)
        y.block_until_ready()
        dt = time.perf_counter() - t0
        gflops = 2 * size**3 * reps / dt / 1e9

        # HBM stream: out = a + b reads 2 arrays and writes 1
        n = 4 * size * size
        a = jnp.ones(n, jnp.float32)
        b = jnp.ones(n, jnp.float32)
        g = jax.jit(lambda u, v: u + v)
        g(a, b).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            c = g(a, b)
        c.block_until_ready()
        dt = time.perf_counter() - t0
        membw = 3 * n * 4 * reps / dt / 1e9

        # collective round: psum of a scalar-per-shard over the rows axis
        ps = jax.jit(_compat_shard_map(lambda v: jax.lax.psum(v, "rows"),
                                   mesh=self.mesh, in_specs=P("rows"),
                                   out_specs=P()))
        vec = jnp.ones(self.n_devices, jnp.float32)
        ps(vec).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(50):
            r = ps(vec)
        r.block_until_ready()
        psum_us = (time.perf_counter() - t0) / 50 * 1e6
        out = {"matmul_gflops": gflops, "membw_gbps": membw,
               "psum_latency_us": psum_us, "size": size}
        from h2o3_tpu.utils import timeline

        timeline.record("self_benchmark", "boot_probe", **{
            k: round(v, 2) for k, v in out.items() if k != "size"})
        return out


# reentrant: extension hooks run under the boot lock (so no other thread
# sees a cluster whose extensions haven't loaded) and may themselves call
# cluster()/init()
_LOCK = threading.RLock()
_CLUSTER: Optional[Cluster] = None


def init(args: Optional[OptArgs] = None, **kw) -> Cluster:
    """Boot (or return) the runtime. h2o.init() parity
    (reference: h2o-py/h2o/h2o.py h2o.init)."""
    global _CLUSTER
    with _LOCK:
        if _CLUSTER is None:
            a = args or OptArgs.from_env()
            for k, v in kw.items():
                setattr(a, k, v)
            _CLUSTER = Cluster(a)
            # extension SPI hooks (ExtensionManager.extensionsLoaded): after
            # _CLUSTER is assigned (hooks use the full public API through
            # the reentrant lock) but before any OTHER thread can observe
            # the cluster — failures are isolated inside the runner
            from h2o3_tpu import extensions as _ext

            _ext.run_extension_hooks(_CLUSTER)
        return _CLUSTER


def cluster() -> Cluster:
    return init()


def cluster_info() -> dict:
    return cluster().info()


def shutdown() -> None:
    """Drop the runtime and all stored keys (h2o.cluster().shutdown())."""
    global _CLUSTER
    from h2o3_tpu.core.dkv import DKV

    with _LOCK:
        if _CLUSTER is not None and getattr(_CLUSTER, "_heartbeat", None):
            _CLUSTER._heartbeat.stop()
        DKV.clear()
        _CLUSTER = None
    # registered extensions re-run their hooks against the next cluster
    from h2o3_tpu import extensions as _ext

    _ext._INITIALIZED.clear()

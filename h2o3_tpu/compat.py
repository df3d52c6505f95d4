"""The one place the framework touches jax APIs that sit outside the
stable `jax.numpy` / `jax.jit` surface: shard_map, the varying-axis cast,
executable (de)serialization, the profiler, Pallas, memory analysis and the
raw StableHLO compile. Written for the one installation there is —
jax 0.9.0 / jaxlib 0.9.0 (pinned in pyproject.toml); each function is the
direct 0.9.0 call. Call sites and the analyzer's `compat-routing` pass
import these names, so an upgrade is edited here and nowhere else."""

from __future__ import annotations


def shard_map(f, **kw):
    """`jax.shard_map` (keywords: mesh, in_specs, out_specs, check_vma)."""
    import jax

    return jax.shard_map(f, **kw)


def pcast(x, axes, to="varying"):
    """`jax.lax.pcast`: annotate a replicated value as varying over mesh
    `axes` (loop carries inside shard_map must be typed like the
    per-shard data they accumulate)."""
    import jax

    return jax.lax.pcast(x, axes, to=to)


# ---------------------------------------------------------------------------
# AOT executable (de)serialization — the artifact/compile-cache substrate.
# ---------------------------------------------------------------------------

def serialize_compiled(compiled):
    """Serialize an AOT-compiled executable (``jit(f).lower(...).compile()``)
    to ``(payload_bytes, in_tree, out_tree)``. Raises when the backend
    cannot serialize it — on a TPU that is an error to see, not a reason
    to quietly ship an artifact without its executable."""
    from jax.experimental import serialize_executable as se

    return se.serialize(compiled)


def compiled_device_ids(compiled):
    """Ids of the devices an AOT-compiled executable runs on — stored next
    to its serialized payload so the load targets the same devices."""
    return [int(d.id) for d in compiled.runtime_executable().local_devices()]


def deserialize_compiled(payload, in_tree, out_tree, device_ids=None):
    """Load a serialized executable back into a callable on the devices it
    was compiled for (`device_ids`; default: the first device — a
    single-device program loaded over every local device would demand one
    argument shard per device). Raises when the payload targets a
    different backend/topology — callers treat that as a cache miss."""
    import jax
    from jax.experimental import serialize_executable as se

    devs = jax.devices()
    if device_ids is None:
        devs = devs[:1]
    else:
        by_id = {d.id: d for d in devs}
        devs = [by_id[i] for i in device_ids]
    return se.deserialize_and_load(payload, in_tree, out_tree,
                                   execution_devices=devs)


def profiler_start(log_dir: str) -> None:
    """``jax.profiler.start_trace``. Raises when a capture is already
    running — the REST layer maps that to a clean 409."""
    import jax

    jax.profiler.start_trace(log_dir)


def profiler_stop() -> None:
    """``jax.profiler.stop_trace`` — raises when no capture is running
    (mapped to a clean 400 at the REST layer)."""
    import jax

    jax.profiler.stop_trace()


def profiler_annotation(name: str):
    """``jax.profiler.TraceAnnotation`` — a named region inside a capture."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def pallas_modules():
    """``(pallas, pallas.tpu)`` — the TPU kernel surface, imported at call
    time so importing the package never pulls Pallas in."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


def memory_analysis(compiled):
    """Byte-level memory estimate of an AOT-compiled executable
    (``compiled.memory_analysis()``), normalized to
    ``{argument_bytes, output_bytes, temp_bytes, generated_code_bytes}``,
    or None when the backend reports none — the compile ledger records it
    as the program's HBM estimate ("Memory Safe Computations with XLA
    Compiler", PAPERS.md)."""
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    return {"argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes)}


def compile_stablehlo(text: str):
    """Compile StableHLO module text through the local XLA client for the
    first device. Returns a loaded executable whose ``.execute([arrays])``
    runs the exact program the exporter lowered, so results stay
    bitwise-identical to the source process."""
    import jax
    from jax.extend import backend as jex_backend
    from jaxlib import xla_client as xc

    d = jax.devices()[0]
    return d.client.compile_and_load(
        text, xc.DeviceList((d,)),
        jex_backend.get_compile_options(num_replicas=1, num_partitions=1))

"""Cluster-wide observability plane (ISSUE 8).

Reference: H2O-3 ships node-level introspection as a first-class subsystem
(water/TimeLine.java ring, /3/Timeline, /3/Logs, WaterMeter, /3/Profiler);
Podracer-style fleets (PAPERS.md) roll per-learner health and throughput up
at the one controller. This package is that layer for the TPU cloud:

- :mod:`h2o3_tpu.obs.metrics` — a process-wide metrics registry
  (counters / gauges / histograms with bounded label sets). Per-process
  snapshots publish through the cloud KV so the coordinator serves
  CLUSTER-wide ``GET /3/Metrics`` in Prometheus text exposition and JSON.
- :mod:`h2o3_tpu.obs.tracing` — trace spans with context propagation: a
  span id minted at REST ingress rides the oplog op record, so
  coordinator publish → follower replay → ack land in ONE span tree
  (``GET /3/Trace/{id}``), and the scoring fast path emits child spans
  for queue-wait / pack / dispatch / blocking-fetch without adding any
  device sync.
- :mod:`h2o3_tpu.obs.flight` — the flight recorder: on a fatal signal, a
  watchdog recovery action, or a bench-stage timeout, the timeline ring +
  open spans + a metrics snapshot persist atomically to
  ``$H2O_TPU_ICE_ROOT/flight/`` (``GET /3/FlightRecords``), so a dark
  bench round leaves a corpse to autopsy instead of a bare timeout.
- :mod:`h2o3_tpu.obs.phases` — the runtime lifecycle phase tracker
  (ISSUE 12): ``backend_init`` … ``server_start`` as deadline-supervised
  timeline phases; a hung phase dumps a flight record naming itself
  and, under ``H2O_TPU_PHASE_DEADLINE_EXIT=1``, exits the process fast.
- :mod:`h2o3_tpu.obs.compiles` — the cluster-wide compile ledger: the
  ONE chokepoint every XLA compile routes through (family, signature,
  duration, cache disposition, HBM estimate), served on
  ``GET /3/Runtime`` and folded into ``/3/Metrics``.

Import cost: this package pulls in only the stdlib — jax and the heavy
framework modules load lazily inside callbacks, so the flight recorder
stays usable from a process whose backend init hangs."""

from h2o3_tpu.obs import (compiles, flight, metrics,  # noqa: F401
                          phases, tracing)

#!/usr/bin/env python3
"""What one ``tracing.span()`` costs the host: ns per enter + exit with no
active trace, with one, and with a profiler capture running (every live span
is then also a TraceMe event). By hand, anywhere: it times host code only.

    python3 bench/tests/span_overhead.py [--iterations 100000]
"""

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)


def ns_per_span(tracing, n: int, traced: bool) -> float:
    """A fresh root every 256 spans keeps every one of them stored, as a
    request's are (the store drops what a trace holds beyond 512)."""
    t_in = 0
    done = 0
    while done < n:
        k = min(256, n - done)
        with tracing.root_span("ingress") if traced else tracing.span("x"):
            t0 = time.perf_counter_ns()
            for _ in range(k):
                with tracing.span("stage", rows=1):
                    pass
            t_in += time.perf_counter_ns() - t0
        done += k
    return t_in / n


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iterations", type=int, default=100_000)
    args = ap.parse_args()
    import jax

    from h2o3_tpu.obs import tracing

    jax.devices()                       # the backend is up, as in a server
    n = args.iterations
    ns_per_span(tracing, 2000, True)    # imports, first TraceMe
    out = {"iterations": n, "platform": jax.devices()[0].platform,
           "no_trace_ns": ns_per_span(tracing, n, False),
           "active_trace_ns": ns_per_span(tracing, n, True)}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["active_trace_capture_ns"] = ns_per_span(tracing, n, True)
        finally:
            jax.profiler.stop_trace()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

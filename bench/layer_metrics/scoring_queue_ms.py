"""Median per request of its admission_wait + queue_wait spans
(obs/tracing), over the request traces the span store still holds."""

from bench.harness import layers


def read(run, name):
    return layers.median_or_none(
        layers.span_ms_per_trace(run, ("admission_wait", "queue_wait")))

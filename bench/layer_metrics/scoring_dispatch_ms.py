"""Median per request trace of its dispatch + fetch spans (obs/tracing);
the flush leader's trace carries them, the requests coalesced behind it do
not and are left out."""

from bench.harness import layers


def read(run, name):
    return layers.median_or_none(
        layers.span_ms_per_trace(run, ("dispatch", "fetch")))

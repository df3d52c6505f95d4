"""Megabytes a shard hands the all-reduces of one training job: the
``psum_bytes`` attributes of the window's ``trees`` and ``metrics`` spans,
summed, over the window's jobs and 10^6. The program counts them on the host
from static shapes (a tree's level histograms, its leaf sums and centering
mean; the metrics pass's partial sums), 0 on a mesh of one device. It moves
when a change adds a collective or widens one, which no one-chip cell sees.
A program without the attribute gives None, never 0."""

from bench.harness import spans

SPANS = ("trees", "metrics")


def read(run, name):
    traces = spans.window_traces(run.system.spans("ingress"),
                                 len(run.window.get("jobs", ())))
    found = [s["attrs"]["psum_bytes"] for t in traces for s in t
             if s.get("name") in SPANS
             and "psum_bytes" in (s.get("attrs") or {})]
    if not found or not traces:
        return None
    return sum(found) / len(traces) / 1e6

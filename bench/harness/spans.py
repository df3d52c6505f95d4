"""Stage times from the program's own span trees (``obs/tracing``).

A trace is the list of finished spans of one REST request: ``ingress`` at
the root and, since the job thread and the scoring flush inherit the
request's context, everything the request caused beneath it. Each per-layer
entry reads some span names (the tables below); a span of any other name
(``compile``, ``oplog.publish`` ...) counts to its nearest ancestor that an
entry reads, so nothing a request did falls out of the sum:

  stage time = sum over the stage's spans of
               (duration - union of the nearest read descendants' intervals,
                clipped to the span)

For a span without read descendants that is its duration; for ``job`` and
``ingress`` it is their self time. A program without these spans (the commit
before they existed) gives ``None``, and the harness leaves the metric out.
"""

from __future__ import annotations

import statistics

from bench.harness.trace import _union

# entry suffix -> the span names it reads
TRAIN_STAGES = {"bin": ("bin",), "trees": ("trees",),
                "assemble": ("assemble",), "metrics": ("metrics",),
                "other": ("job",)}
SCORE_STAGES = {"ingress": ("ingress",),
                "queue": ("admission_wait", "queue_wait"),
                "flush": ("flush",), "pack": ("adapt", "pack"),
                "dispatch": ("dispatch",), "fetch": ("fetch",),
                "metrics": ("metrics",)}
WINDOW_PATHS = ("/3/ModelBuilders/", "/3/Predictions/")


def _read_below(span: dict, children: dict, read: set) -> list:
    """[start, end] of the nearest descendants of ``span`` whose name an
    entry reads (what lies below an unread span is looked through)."""
    out = []
    for c in children.get(span["span_id"], ()):
        if c["name"] in read:
            out.append([c["start_ms"], c["end_ms"]])
        else:
            out.extend(_read_below(c, children, read))
    return out


def _uncovered(span: dict, intervals: list) -> float:
    """ms of ``span`` that none of ``intervals`` (clipped to it) covers."""
    lo, hi = span["start_ms"], span["end_ms"]
    cover = _union([[max(a, lo), min(b, hi)] for a, b in intervals
                    if min(b, hi) > max(a, lo)])
    return (hi - lo) - sum(b - a for a, b in cover)


def self_ms(span: dict, spans: list) -> float:
    """Self time of one span: its duration less the union of its children's
    intervals clipped to it (a child may outlive its parent: ``job`` under
    ``ingress``)."""
    return _uncovered(span, [[c["start_ms"], c["end_ms"]]
                             for c in spans
                             if c.get("parent_id") == span["span_id"]])


def stage_ms(spans: list, names, read: set):
    """Summed ms one trace spent in the spans called one of ``names``, less
    what their read descendants cover; None when the trace has no such
    span."""
    children = {}
    for s in spans:
        children.setdefault(s.get("parent_id"), []).append(s)
    mine = [s for s in spans if s["name"] in names]
    if not mine:
        return None
    return sum(_uncovered(s, _read_below(s, children, read)) for s in mine)


def window_traces(traces: list, n: int) -> list:
    """The newest ``n`` of ``traces`` (newest first, as the span store lists
    them) whose root is a train or a predict POST: the warm-up job and the
    warm-up requests are older and stay out, and so does whatever else the
    run asked over REST."""
    out = []
    for spans in traces:
        if len(out) >= n:
            break
        root = next((s for s in spans if not s.get("parent_id")), None)
        path = ((root or {}).get("attrs") or {}).get("path", "")
        if path.startswith(WINDOW_PATHS):
            out.append(spans)
    return out


def train_stage_s(run, name: str):
    """Median over the window's jobs of the seconds in one stage."""
    names = TRAIN_STAGES[name.split(".", 1)[1]]
    read = {n for ns in TRAIN_STAGES.values() for n in ns}
    traces = window_traces(run.system.spans("ingress"),
                           len(run.window.get("jobs", ())))
    per_job = [ms for ms in (stage_ms(t, names, read) for t in traces)
               if ms is not None]
    return statistics.median(per_job) / 1e3 if per_job else None


def score_stage_ms(run, name: str):
    """Total ms of one stage over the window's requests, divided by the
    requests: the stages of a cell then add up to the mean time a request
    spent inside the program, whoever led its flush."""
    names = SCORE_STAGES[name.split(".", 1)[1]]
    read = {n for ns in SCORE_STAGES.values() for n in ns}
    traces = window_traces(run.system.spans("ingress"),
                           int(run.window.get("attempted", 0)))
    per_req = [ms for ms in (stage_ms(t, names, read) for t in traces)
               if ms is not None]
    return sum(per_req) / len(traces) if per_req else None

"""Trace spans with cross-process context propagation.

Reference: H2O-3's TimeLine ring records per-node wire events but has no
request identity — you cannot follow one REST call through the cloud. Here
a trace id is minted at REST ingress (api/server.py wraps every handler in
a root span), rides the oplog op record (``parallel/oplog.py`` attaches
``{"trace": {trace_id, span_id}}`` to ``publish``), and the follower's
replay + ack land as children of the coordinator's publish span — so
coordinator publish → follower replay → ack form ONE span tree,
retrievable from ``GET /3/Trace/{trace_id}``.

A trace follows its request onto other threads: ``core/job.py`` captures
the POST's context and runs the job body under it (span ``job``, with the
builders' stage spans ``bin`` / ``trees`` / ``assemble`` / ``metrics``
beneath), and the micro-batcher's flush leader records ``queue_wait`` and
``flush`` into every coalesced request's own trace. The scoring fast path
emits ``adapt`` / ``pack`` / ``dispatch`` / ``fetch`` / ``metrics`` under
``flush``, and one span a sequential phase of the flush where the host does
the work: ``view`` (an entry's shard view), ``parts`` (the coalesced arm's
chunk loop, over its ``pack`` spans), ``windows`` (the whole window loop,
over the memory planner's ``plan`` and its ``dispatch`` spans; never a span
a window: what a window costs rides as summed attributes), ``join`` (the
outputs' concatenation) and
``lift`` (an entry's rows back out to its frame's layout).
None of them adds a device synchronization: a span is host time
around calls the path already makes and ends where the host already blocks
(the fused-path ``gathered_rows``/compile counters assert the path itself
is unchanged — see tests).

One clock: ``now_ms()`` is ``time.perf_counter_ns()`` laid on the epoch
once, at import, so timestamps read like wall time and never step. Every
live span is also a ``jax.profiler.TraceAnnotation`` named
``h2o3.<span name>``: under a profiler capture (``POST /3/Profiler/start``)
the program's spans lie on the host planes of the same ``.xplane.pb`` as
the device ops; with no capture running that is a TraceMe no-op.

Cost model: ``span()`` is a no-op (no allocation, no store write) unless
the calling thread has an ACTIVE trace — library-mode predict() pays one
thread-local read. The store is bounded (``H2O_TPU_OBS_TRACE_CAP`` traces
× ``_SPAN_CAP`` spans, oldest trace evicted; what either bound turns away
is counted, ``h2o3_trace_dropped_total{what}``) and follower-side spans from
replayed ops additionally publish to the cloud KV (bounded, self-GCing)
so the coordinator can serve the full tree."""

from __future__ import annotations

import collections
import json
import os
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from h2o3_tpu import compat     # jax only inside its functions

_SPAN_CAP = 512                 # spans kept per trace
_KV_PREFIX = "obs/span/"
_KV_KEEP = 512                  # remote-published span keys kept in the KV

_TLS = threading.local()        # .stack: live _SpanCtx / activate frames
_LOCK = threading.Lock()
# trace_id -> list of finished span dicts (insertion-ordered eviction)
_STORE: "collections.OrderedDict[str, List[dict]]" = collections.OrderedDict()
_PUBLISHED: "collections.deque[str]" = collections.deque()


def trace_cap() -> int:
    # 1,024: a traced 40 s window of gbm_batch_score makes over 300 traces
    # since PR 37, and a window's readers need 95% of them still held
    try:
        return max(int(os.environ.get("H2O_TPU_OBS_TRACE_CAP", "1024")), 1)
    except ValueError:
        return 1024


# epoch ns at perf_counter 0: taken once, so the clock below never steps
_EPOCH_NS = time.time_ns() - time.perf_counter_ns()


def now_ms() -> float:
    """Monotonic ms that read like ``time.time() * 1000``: the one clock of
    every span, and of callers that time a wait themselves for
    ``record_span``."""
    return (_EPOCH_NS + time.perf_counter_ns()) / 1e6


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def current() -> Optional[dict]:
    st = getattr(_TLS, "stack", None)
    return st[-1].span if st else None


def current_trace_id() -> Optional[str]:
    cur = current()
    return cur["trace_id"] if cur else None


def context() -> Optional[Dict[str, str]]:
    """The active span as a propagation context ({trace_id, span_id}) —
    what rides the oplog op record and the micro-batcher's entries."""
    cur = current()
    if cur is None:
        return None
    return {"trace_id": cur["trace_id"], "span_id": cur["span_id"]}


def _proc_index() -> int:
    try:
        import jax

        return jax.process_index()
    except Exception:   # noqa: BLE001 — pre-init
        return 0


def _store(span: dict) -> None:
    """Bounded-store insert (oldest trace evicted) + the span counter —
    the single copy both the context-manager finish path and the
    explicitly-timed record_span path go through. What the bounds cost is
    counted: a span a full trace turns away (a request's root finishes
    last, so it is the first to go) and a trace the ring evicts."""
    tid = span["trace_id"]
    evicted = 0
    with _LOCK:
        spans = _STORE.get(tid)
        if spans is None:
            spans = _STORE[tid] = []
            while len(_STORE) > trace_cap():
                _STORE.popitem(last=False)
                evicted += 1
        kept = len(spans) < _SPAN_CAP
        if kept:
            spans.append(span)
    from h2o3_tpu.obs import metrics

    metrics.inc("h2o3_trace_spans_total")
    if not kept:
        metrics.inc("h2o3_trace_dropped_total", what="span")
    if evicted:
        metrics.inc("h2o3_trace_dropped_total", evicted, what="trace")


def _finish(span: dict) -> None:
    span["end_ms"] = round(now_ms(), 3)
    span["ms"] = round(span["end_ms"] - span["start_ms"], 3)
    _store(span)


def _kv_publish(span: dict) -> None:
    """Ship a finished follower-side span to the cloud KV so the
    coordinator's ``/3/Trace/{id}`` can merge it; bounded self-GC."""
    from h2o3_tpu.parallel import distributed as D

    key = f"{_KV_PREFIX}{span['trace_id']}/{span['proc']}_{span['span_id']}"
    try:
        if not D.kv_put(key, json.dumps(span)):
            return
    except Exception:   # noqa: BLE001 — best-effort by contract
        return
    expired = []
    with _LOCK:
        _PUBLISHED.append(key)
        while len(_PUBLISHED) > _KV_KEEP:
            expired.append(_PUBLISHED.popleft())
    # KV round-trips stay OUTSIDE the span-store lock: a slow delete must
    # not stall span recording on every other thread
    for old in expired:
        try:
            D.kv_delete(old)
        except Exception:   # noqa: BLE001
            pass


def _new_span(name: str, trace_id: str, parent_id: Optional[str],
              attrs: Dict[str, Any]) -> dict:
    return {"trace_id": trace_id, "span_id": uuid.uuid4().hex[:12],
            "parent_id": parent_id, "name": name,
            "proc": _proc_index(), "start_ms": round(now_ms(), 3),
            "status": "ok",
            "attrs": {k: v for k, v in attrs.items() if v is not None}}


class _SpanCtx:
    """Context manager over one span; ``None``-like when tracing is
    inactive (``bool(span_cm)`` is False and ``ctx()`` returns None)."""

    __slots__ = ("span", "_ann")

    def __init__(self, span: Optional[dict]):
        self.span = span
        self._ann = None

    def __bool__(self):
        return self.span is not None

    def ctx(self) -> Optional[Dict[str, str]]:
        if self.span is None:
            return None
        return {"trace_id": self.span["trace_id"],
                "span_id": self.span["span_id"]}

    def set(self, **attrs) -> None:
        if self.span is not None:
            self.span["attrs"].update(attrs)

    def _open(self) -> None:
        _stack().append(self)
        self._ann = compat.profiler_annotation("h2o3." + self.span["name"])
        self._ann.__enter__()

    def _close(self) -> None:
        self._ann.__exit__(None, None, None)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        _finish(self.span)

    def __enter__(self):
        if self.span is not None:
            self._open()
        return self

    def __exit__(self, et, ev, tb):
        if self.span is None:
            return False
        if et is not None:
            self.span["status"] = "error"
            self.span["attrs"]["error"] = f"{et.__name__}: {ev}"[:500]
        self._close()
        return False

    def advance(self, name: str, **attrs) -> None:
        """End this span now and go on as its sibling `name`, which the
        enclosing ``with`` then closes: one stage handing over to the next
        at a point inside a callee (a fit loop's last tree read)."""
        if self.span is None:
            return
        done = self.span
        self._close()
        self.span = _new_span(name, done["trace_id"], done["parent_id"],
                              attrs)
        self.span["start_ms"] = done["end_ms"]
        self._open()


def root_span(name: str, **attrs) -> _SpanCtx:
    """Mint a new trace (REST ingress). Always records."""
    return _SpanCtx(_new_span(name, uuid.uuid4().hex[:16], None, attrs))


def span(name: str, **attrs) -> _SpanCtx:
    """Child of the calling thread's active span; inert no-op when no
    trace is active (the library-mode fast path pays one TLS read)."""
    cur = current()
    if cur is None:
        return _SpanCtx(None)
    return _SpanCtx(_new_span(name, cur["trace_id"], cur["span_id"], attrs))


def advance(name: str, **attrs) -> None:
    """``_SpanCtx.advance`` on the calling thread's innermost live span;
    nothing when there is none (or only an adopted context)."""
    st = getattr(_TLS, "stack", None)
    if st and isinstance(st[-1], _SpanCtx):
        st[-1].advance(name, **attrs)


def set_attrs(**attrs) -> None:
    """``_SpanCtx.set`` on the calling thread's innermost live span (one
    that a callee opened or advanced to); nothing when there is none."""
    st = getattr(_TLS, "stack", None)
    if st and isinstance(st[-1], _SpanCtx):
        st[-1].set(**attrs)


def add_attrs(**counts) -> None:
    """Add each count to the attribute of that name (0 where there is none
    yet) on the calling thread's innermost live span: a sum over what a
    stage dispatched, kept on the host."""
    st = getattr(_TLS, "stack", None)
    if st and isinstance(st[-1], _SpanCtx) and st[-1].span is not None:
        attrs = st[-1].span["attrs"]
        for k, n in counts.items():
            attrs[k] = attrs.get(k, 0) + n


class activate:
    """Adopt a propagation context on THIS thread (the micro-batcher's
    flush leader runs on a different thread than the submitting request):
    nested ``span()`` calls attach under `ctx`. No-op for a None ctx."""

    def __init__(self, ctx: Optional[Dict[str, str]]):
        self._ok = isinstance(ctx, dict) and bool(ctx.get("trace_id"))
        self.span = ({"trace_id": str(ctx["trace_id"]),
                      "span_id": ctx.get("span_id")} if self._ok else None)

    def __enter__(self):
        if self._ok:
            _stack().append(self)
        return self

    def __exit__(self, et, ev, tb):
        if self._ok:
            st = _stack()
            if st and st[-1] is self:
                st.pop()
        return False


def record_span(name: str, ctx: Optional[Dict[str, str]], start_ms: float,
                end_ms: Optional[float] = None, publish: bool = False,
                status: str = "ok", **attrs) -> Optional[dict]:
    """Append an already-timed span (explicit ``now_ms()`` timestamps)
    under `ctx`, returning it — the queue-wait span is recorded by the
    flush leader on behalf of each waiting request's trace, and the
    follower's replay/ack spans are recorded AFTER the ack (with
    `publish=True` so they cross the KV to the trace's home process)."""
    if not isinstance(ctx, dict) or not ctx.get("trace_id"):
        return None
    sp = _new_span(name, str(ctx["trace_id"]), ctx.get("span_id"), attrs)
    sp["status"] = status
    sp["start_ms"] = round(float(start_ms), 3)
    sp["end_ms"] = round(float(end_ms if end_ms is not None
                               else now_ms()), 3)
    sp["ms"] = round(sp["end_ms"] - sp["start_ms"], 3)
    _store(sp)
    if publish:
        _kv_publish(sp)
    return sp


def get_trace(trace_id: str, include_remote: bool = True) -> List[dict]:
    """Every finished span recorded for `trace_id`: local store + (on a
    cloud) the KV-published follower spans, start-ordered."""
    with _LOCK:
        spans = list(_STORE.get(trace_id, ()))
    if include_remote:
        from h2o3_tpu.parallel import distributed as D

        seen = {s["span_id"] for s in spans}
        for _k, v in D.kv_dir(f"{_KV_PREFIX}{trace_id}/"):
            try:
                sp = json.loads(v)
            except (ValueError, TypeError):
                continue
            if isinstance(sp, dict) and sp.get("span_id") not in seen:
                spans.append(sp)
    return sorted(spans, key=lambda s: s.get("start_ms", 0.0))


def span_tree(spans: List[dict]) -> List[dict]:
    """Nest spans by parent_id: [{**span, children: [...]}] roots. Spans
    whose parent never finished (open at dump time) surface as roots."""
    nodes = {s["span_id"]: dict(s, children=[]) for s in spans}
    roots = []
    for s in spans:
        node = nodes[s["span_id"]]
        parent = nodes.get(s.get("parent_id"))
        if parent is not None:
            parent["children"].append(node)
        else:
            roots.append(node)
    return roots


def recent_traces(n: int = 50) -> List[dict]:
    """Newest trace ids with their root span names (for GET /3/Trace)."""
    with _LOCK:
        items = list(_STORE.items())[-n:]
    out = []
    for tid, spans in reversed(items):
        root = next((s for s in spans if not s.get("parent_id")), None)
        out.append({"trace_id": tid, "spans": len(spans),
                    "root": (root or {}).get("name"),
                    "start_ms": min((s.get("start_ms", 0.0) for s in spans),
                                    default=0.0)})
    return out


def open_spans() -> List[dict]:
    """The calling thread's active (unfinished) spans — flight-recorder
    fodder. Cross-thread open spans are not visible by design (no global
    registry of live stacks; the store holds everything finished)."""
    return [dict(f.span) for f in getattr(_TLS, "stack", [])]


def clear() -> None:
    """Drop the span store (tests)."""
    with _LOCK:
        _STORE.clear()

"""What ``score_stage_ms.flush`` is made of, ms a request: the self time of
``flush`` split by the spans the program opens inside a flush (ISSUE 36;
``h2o3_tpu/scoring.py``). Totals over the window's requests ÷ requests, as
``score_stage_ms`` reckons, so the parts add up to ``score_stage_ms.flush``:

  ``.wait``     a request coalesced behind a lead: its ``flush`` span names
                the ``lead`` and has no children, the whole interval is wait
  ``.view``     per entry, the ShardedFrame view (and the pipeline's look)
  ``.parts``    coalesced arm: the loop over entries and bucket chunks, less
                the ``pack`` spans in it: what ``pack_features`` does before
                its executable (two device scalars, the dtype tuple, the
                lookup) and the eager ``Xd[:m]`` of a tail
  ``.plan``     ``budget.plan`` at the head of the window loop: the
                device's memory statistics and the store's residency scan
  ``.windows``  the window loop less ``plan`` and its ``dispatch`` (and
                ``pack``) spans: slice, pad, reshard, executable lookup,
                ``[:m]`` on each output
  ``.join``     ``jnp.concatenate`` of the windows' outputs
  ``.lift``     per entry, its rows of the margins, pad + reshard, margin->raw
  ``.self``     what of a lead's ``flush`` none of these nor a stage covers:
                the oplog turn, ``record_batch``, the timeline, loop glue

``.rebucket`` is the ``rebucket_ms`` attribute of ``windows`` (slice + pad +
reshard, two clock reads a window): a part of ``.windows``, not of the sum.

A program without the spans (the commit before them) gives None for every
entry, never 0; with them, a part no request of the window took (no follower:
``.wait``) is a true 0. A window whose traces the span store no longer holds
(under 95% of ``attempted`` found) gives None rather than a mean over what
was left."""

from bench.harness import spans

PHASES = ("view", "parts", "plan", "windows", "join", "lift")
READ = {n for ns in spans.SCORE_STAGES.values() for n in ns} | set(PHASES)
FOUND_SHARE = 0.95


def window_traces(run):
    """The window's request traces (newest first), or None where the store
    has lost more than 5% of them."""
    n = int(run.window.get("attempted", 0))
    traces = spans.window_traces(run.system.spans("ingress"), n)
    if not traces or len(traces) < FOUND_SHARE * n:
        return None
    return traces


def is_follower(trace) -> bool:
    return any(s["name"] == "flush" and "lead" in (s.get("attrs") or {})
               for s in trace)


def read(run, name):
    part = name.split(".", 1)[1]
    traces = window_traces(run)
    if traces is None or not any(s["name"] == "windows"
                                 for t in traces for s in t):
        return None
    total = 0.0
    for t in traces:
        if part == "rebucket":
            total += sum((s.get("attrs") or {}).get("rebucket_ms", 0.0)
                         for s in t if s["name"] == "windows")
        elif part in ("wait", "self"):
            if is_follower(t) == (part == "wait"):
                total += spans.stage_ms(t, ("flush",), READ) or 0.0
        else:
            total += spans.stage_ms(t, (part,), READ) or 0.0
    return total / len(traces)

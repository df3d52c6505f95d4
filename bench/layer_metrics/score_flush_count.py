"""Which mode a run of the scoring cell was in, from the attributes of the
window's lead ``flush`` spans (a follower's copy names its ``lead`` and is
left out):

  ``.requests``  mean ``requests`` a flush: 1.0 = every request alone, 2.0 =
                 the two closed-loop clients in step, every flush both
  ``.windows``   mean row windows a flush dispatched (``windows`` of the
                 ``windows`` span beneath it): 61 for one 1M-row request at
                 16,384 rows a window, 122 (or 123) for two

Their direction decides nothing; they name the mode. ``.windows`` is None on
a program without the span; both are None where the store has lost more than
5% of the window's traces (``score_flush_ms.window_traces``)."""

from bench.layer_metrics import score_flush_ms


def read(run, name):
    part = name.split(".", 1)[1]
    traces = score_flush_ms.window_traces(run)
    if traces is None:
        return None
    leads = [t for t in traces if not score_flush_ms.is_follower(t)
             and any(s["name"] == "flush" for s in t)]
    if not leads:
        return None
    if part == "requests":
        found = [s["attrs"]["requests"] for t in leads for s in t
                 if s["name"] == "flush" and "requests" in s["attrs"]]
    else:
        found = [s["attrs"]["windows"] for t in leads for s in t
                 if s["name"] == "windows" and "windows" in s["attrs"]]
    return sum(found) / len(leads) if found else None

"""Share of the routing levels of the window's trees whose tables the tree
program read by select, not by gather (100 x (1 - ``route_gather_levels`` /
``route_levels``)), from the attributes of the window's ``trees`` spans: the
program counts them on the host from each tree's static level widths. A
program without the attributes gives None, never 0."""

from bench.harness import spans


def read(run, name):
    traces = spans.window_traces(run.system.spans("ingress"),
                                 len(run.window.get("jobs", ())))
    attrs = [s.get("attrs") or {} for t in traces for s in t
             if s.get("name") == "trees"]
    levels = sum(a.get("route_levels", 0) for a in attrs)
    if levels <= 0:
        return None
    return 100.0 * (1.0 - sum(a.get("route_gather_levels", 0)
                              for a in attrs) / levels)

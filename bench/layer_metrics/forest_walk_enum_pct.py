"""Share of the window's forest-walk dispatches that took the categorical
form (a label of ``h2o3_forest_walk_total{form}`` that ends in ``+cat``),
from the counter as the driver read it over ``GET /3/Metrics`` at the
window's start and end. A program without the counter gives None, never 0."""


def read(run, name):
    walks = (run.window.get("counters") or {}).get("h2o3_forest_walk_total")
    total = sum(walks.values()) if walks else 0.0
    if total <= 0:
        return None
    return 100.0 * sum(v for form, v in walks.items()
                       if form.endswith("+cat")) / total

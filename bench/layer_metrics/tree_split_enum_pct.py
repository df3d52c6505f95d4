"""Share of the splits the window's jobs made that are categorical (a subset
of levels), from the program's counter ``h2o3_tree_splits_total{kind}``,
which the driver read over ``GET /3/Metrics`` at the window's start and end.
A program without the counter gives None, never 0."""


def read(run, name):
    made = (run.window.get("counters") or {}).get("h2o3_tree_splits_total")
    total = sum(made.values()) if made else 0.0
    if total <= 0:
        return None
    return 100.0 * made.get("enum", 0.0) / total

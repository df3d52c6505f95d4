"""IRLS iterations a job of the window took, from the program's counter
``h2o3_glm_iterations_total``, which the driver read over ``GET /3/Metrics``
at the window's start and end: a rate that moved because the count moved
says so here. A program without the counter gives None, never 0."""


def read(run, name):
    made = (run.window.get("counters") or {}).get("h2o3_glm_iterations_total")
    jobs = len(run.window.get("jobs", ()))
    total = sum(made.values()) if made else 0.0
    if total <= 0 or not jobs:
        return None
    return total / jobs

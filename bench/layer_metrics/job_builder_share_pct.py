"""Share of a job's wall that the builder's own clock covers
(model._output.run_time_ms: the tree loop; the rest is binning, metrics and
REST), over the window's finished jobs."""


def read(run, name):
    jobs = [j for j in run.window.get("jobs", []) if j["status"] == "DONE"
            and j.get("builder_ms") is not None]
    wall = sum(j["seconds"] for j in jobs)
    if not jobs or wall <= 0:
        return None
    return 100.0 * sum(j["builder_ms"] for j in jobs) / 1e3 / wall

"""Seconds of a DeepLearning training job by stage, from the program's span
tree (ingress -> job -> design / epochs / metrics; ``other`` is the self
time of ``job``): the median over the window's jobs, by
bench/harness/spans.py's rule, so the stages add up to the ``job`` span.
The requests made after the window (``posts_after_window``: the check's
predictions and short job) are newer than the window's jobs and are left
out. A program without these spans gives None."""

import statistics

from bench.harness import spans

STAGES = {"design": ("design",), "epochs": ("epochs",),
          "metrics": ("metrics",), "other": ("job",)}


def stage_s(traces: list, stage: str):
    """Median over ``traces`` (one list of spans a job) of the seconds in
    ``stage``; None where no trace has such a span."""
    read = {n for ns in STAGES.values() for n in ns}
    per_job = [ms for ms in (spans.stage_ms(t, STAGES[stage], read)
                             for t in traces) if ms is not None]
    return statistics.median(per_job) / 1e3 if per_job else None


def window_job_traces(run) -> list:
    jobs = len(run.window.get("jobs", ()))
    after = int(run.window.get("posts_after_window", 0))
    return spans.window_traces(run.system.spans("ingress"),
                               jobs + after)[after:]


def read(run, name):
    return stage_s(window_job_traces(run), name.split(".", 1)[1])

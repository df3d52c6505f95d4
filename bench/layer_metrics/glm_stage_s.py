"""Seconds of a GLM training job by stage, from the program's span tree
(ingress -> job -> design / irls / metrics; ``other`` is the self time of
``job``): the median over the window's jobs, by bench/harness/spans.py's
rule, so the stages add up to the ``job`` span. A program without these
spans gives None."""

import statistics

from bench.harness import spans

STAGES = {"design": ("design",), "irls": ("irls",),
          "metrics": ("metrics",), "other": ("job",)}


def stage_s(traces: list, stage: str):
    """Median over ``traces`` (one list of spans a job) of the seconds in
    ``stage``; None where no trace has such a span."""
    read = {n for ns in STAGES.values() for n in ns}
    per_job = [ms for ms in (spans.stage_ms(t, STAGES[stage], read)
                             for t in traces) if ms is not None]
    return statistics.median(per_job) / 1e3 if per_job else None


def read(run, name):
    traces = spans.window_traces(run.system.spans("ingress"),
                                 len(run.window.get("jobs", ())))
    return stage_s(traces, name.split(".", 1)[1])

"""Seconds of a training job by stage, from the program's span tree
(ingress -> job -> bin / trees / assemble / metrics; ``other`` is the self
time of ``job``): see bench/harness/spans.py."""

from bench.harness import spans


def read(run, name):
    return spans.train_stage_s(run, name)

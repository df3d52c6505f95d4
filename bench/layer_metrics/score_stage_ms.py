"""Mean ms of a scoring request by stage, from the program's span tree
(ingress -> admission_wait / queue_wait / flush -> adapt / pack / dispatch /
fetch / metrics): see bench/harness/spans.py."""

from bench.harness import spans


def read(run, name):
    return spans.score_stage_ms(run, name)

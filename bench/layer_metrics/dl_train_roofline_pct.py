"""Roofline share of the DeepLearning training program
(bench/roofline/dl_train.py: the need is the deployment's shapes', however
a step is computed): the runs of the program that the traced slice holds,
each of the mean steps a run the program's counters give over the window.
A program without those counters gives None."""

from bench.harness import layers
from bench.roofline import dl_train


def read(run, name):
    per_run = dl_train.steps_a_run(run.window)
    if not per_run:
        return None
    return layers.kernel_roofline_pct(run, "dl_train", steps_a_run=per_run)

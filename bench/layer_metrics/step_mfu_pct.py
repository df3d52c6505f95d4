"""The whole step against the chip's peak (see harness/layers.step_mfu_pct
and the formulas in bench/roofline/)."""

from bench.harness import layers


def read(run, name):
    return layers.step_mfu_pct(run)

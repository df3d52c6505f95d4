"""Roofline share of the one-tree program (bench/roofline/tree_grow.py)."""

from bench.harness import layers


def read(run, name):
    return layers.kernel_roofline_pct(run, "tree_grow")

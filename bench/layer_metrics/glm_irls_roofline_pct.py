"""Roofline share of the IRLS program (bench/roofline/glm_irls.py), with the
iterations the trained model reports."""

from bench.harness import layers


def read(run, name):
    its = run.window.get("iterations")
    if not its:
        return None
    return layers.kernel_roofline_pct(run, "glm_irls", iterations=int(its))

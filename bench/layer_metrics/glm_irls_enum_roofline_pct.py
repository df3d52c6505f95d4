"""Roofline share of the IRLS program on a one-hot design
(bench/roofline/glm_irls_enum.py: the need is the deployment's shapes',
however the Gram is computed), with the iterations the trained model
reports."""

from bench.harness import layers


def read(run, name):
    its = run.window.get("iterations")
    if not its:
        return None
    return layers.kernel_roofline_pct(run, "glm_irls_enum",
                                      iterations=int(its))

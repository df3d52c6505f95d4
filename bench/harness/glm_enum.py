"""What the GLM cell on enum columns needs of the program, beside
``bench/harness/system.py`` and ``forest_enum.py`` (whose training frame it
shares): a reader of the trained model that keeps what a GLM job reports
beside its coefficients.

Like ``system.py`` this module touches the program; nothing else the cell
brings does.
"""

from __future__ import annotations


def read_glm(system, model_id: str) -> dict:
    """Coefficients on the original scale (intercept under 'Intercept'),
    the iterations IRLS took, and the residual and null deviance the model
    carries."""
    m = system.model(model_id)
    return {"coef": {k: float(v) for k, v in m.coef().items()},
            "iterations": int(getattr(m, "iterations", 0) or 0),
            "residual_deviance": float(m.residual_deviance),
            "null_deviance": float(m.null_deviance)}

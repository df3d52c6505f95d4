"""What the DeepLearning cell on enum columns needs of the program, beside
``bench/harness/system.py`` and ``forest_enum.py`` (whose training frame it
shares): a reader of the trained model's weights.

Like ``system.py`` this module touches the program; nothing else the cell
brings does.
"""

from __future__ import annotations

import numpy as np


def read_dl(system, model_id: str) -> dict:
    """The layers' weights and biases as float32 numpy, first layer first
    (W is (fan_in, fan_out), its rows in the design's order: every level of
    every enum column, then the numeric columns), and the epochs trained."""
    m = system.model(model_id)
    return {"weights": [(np.asarray(W, np.float32), np.asarray(b, np.float32))
                        for W, b in m.params_tree],
            "epochs_trained": float(m.epochs_trained)}

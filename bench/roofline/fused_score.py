"""What scoring rows through a forest needs, from shapes: one read of the
features (features x 4 B a row), one write of the predictions (label and two
probabilities, 3 x 4 B a row); one compare and one select per (row, tree,
level).

    bytes = rows * (features * 4 + 12)
    flops = 2 * rows * ntrees * max_depth
"""

from __future__ import annotations


def rows_needed(rows: int, features: int, ntrees: int, max_depth: int) -> dict:
    return {"flops": float(2 * rows * ntrees * max_depth),
            "bytes": float(rows * (features * 4 + 12))}


def program_needed(cfg: dict, rows: int, runs: int = 1) -> dict:
    p = cfg["params"]
    one = rows_needed(rows, int(cfg["features"]), int(p["ntrees"]),
                      int(p["max_depth"]))
    return {k: v * runs for k, v in one.items()}


def step_needed(cfg: dict, rows: int, work: dict) -> dict:
    """``work['rows_scored']`` rows went through the forest in the window."""
    return program_needed(cfg, int(work["rows_scored"]))

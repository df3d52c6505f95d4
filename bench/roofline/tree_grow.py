"""What growing histogram trees needs, from shapes — whatever implements it.

Per level of a tree: one read of the bin matrix at its stored width
(rows x features x bin_bytes), of each row's node id (4 B) and of the two
gradient statistics (2 x 4 B); three adds per (row, feature) — the count and
the two statistics into their histogram cell.
Per tree: one read and one write of the margin (2 x 4 B a row).
Per job, once: binning reads the features (features x 4 B a row) and writes
the bin matrix (features x bin_bytes a row).

    level_bytes = rows * (features * bin_bytes + 4 + 8)
    level_flops = 3 * rows * features
    tree  = max_depth * level + rows * 8 bytes
    job   = ntrees * tree + rows * features * (4 + bin_bytes) bytes
"""

from __future__ import annotations


def tree_needed(rows: int, features: int, max_depth: int,
                bin_bytes: int = 1) -> dict:
    level_bytes = rows * (features * bin_bytes + 4 + 8)
    level_flops = 3 * rows * features
    return {"flops": float(max_depth * level_flops),
            "bytes": float(max_depth * level_bytes + rows * 8)}


def program_needed(cfg: dict, rows: int, runs: int) -> dict:
    """``runs`` executions of the one-tree program."""
    p = cfg["params"]
    one = tree_needed(rows, int(cfg["features"]), int(p["max_depth"]))
    return {k: v * runs for k, v in one.items()}


def step_needed(cfg: dict, rows: int, work: dict) -> dict:
    """All the jobs a window finished."""
    p = cfg["params"]
    jobs = int(work["jobs_done"])
    trees = program_needed(cfg, rows, jobs * int(p["ntrees"]))
    binning = rows * int(cfg["features"]) * (4 + 1) * jobs
    return {"flops": trees["flops"], "bytes": trees["bytes"] + binning}

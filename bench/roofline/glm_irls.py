"""What IRLS needs, from shapes. Per iteration: one read of the design
matrix (rows x p x 4 B) and of the response (4 B a row); the Gram's
2 * rows * (p + 1)^2 FLOPs and 4 * rows * (p + 1) for the linear predictor
and X'Wz.

    iter_bytes = rows * (p * 4 + 4)
    iter_flops = 2 * rows * (p + 1)^2 + 4 * rows * (p + 1)
"""

from __future__ import annotations


def iteration_needed(rows: int, p: int) -> dict:
    return {"flops": float(2 * rows * (p + 1) ** 2 + 4 * rows * (p + 1)),
            "bytes": float(rows * (p * 4 + 4))}


def program_needed(cfg: dict, rows: int, runs: int, iterations: int = None) -> dict:
    """``runs`` executions of the IRLS program of ``iterations`` each."""
    its = int(iterations or cfg["params"]["max_iterations"])
    one = iteration_needed(rows, int(cfg["features"]))
    return {k: v * runs * its for k, v in one.items()}


def step_needed(cfg: dict, rows: int, work: dict) -> dict:
    return program_needed(cfg, rows, int(work["jobs_done"]),
                          int(work.get("iterations") or 1))

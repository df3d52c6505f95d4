"""What IRLS on a one-hot design needs, from the deployment's shapes and
whatever implements it. A row of C columns has C + 1 non-zero design entries
(one a column and the intercept), so per iteration:

  bytes: one read of the stored columns (an enum code in its narrowest
    signed integer, 1 B up to 126 levels and 2 B beyond; a numeric column
    4 B), of the response (1 B) and of the row's weight and offset (2 x 4 B);
  FLOPs: the Gram's 2 * (C + 1)^2 a row (the non-zeros' products and adds)
    and 4 * (C + 1) a row for the linear predictor and X'Wz.

    iter_bytes = rows * (stored_row_bytes + 1 + 8)
    iter_flops = 2 * rows * (C + 1)^2 + 4 * rows * (C + 1)

17 + 8 = 25 B and 2 * 81 + 36 = 198 FLOP a row for the airline table's
eight columns: bound by bytes on every chip in ``bench/peaks.json``. A dense
Gram does 2 * 669^2 FLOP a row and reads well under 1% here; a Gram of
weighted co-occurrence sums can come near the need, never over it.
"""

from __future__ import annotations


def stored_row_bytes(cfg: dict) -> int:
    return sum((1 if int(c["levels"]) <= 126 else 2)
               if c["type"] == "enum" else 4 for c in cfg["columns"])


def iteration_needed(cfg: dict, rows: int) -> dict:
    nz = len(cfg["columns"]) + 1
    return {"flops": float(2 * rows * nz ** 2 + 4 * rows * nz),
            "bytes": float(rows * (stored_row_bytes(cfg) + 1 + 8))}


def program_needed(cfg: dict, rows: int, runs: int,
                   iterations: int = None) -> dict:
    """``runs`` executions of the IRLS program of ``iterations`` each."""
    its = int(iterations or cfg["params"]["max_iterations"])
    one = iteration_needed(cfg, rows)
    return {k: v * runs * its for k, v in one.items()}


def step_needed(cfg: dict, rows: int, work: dict) -> dict:
    """All the jobs a window finished, each of the iterations the trained
    model reports."""
    return program_needed(cfg, rows, int(work["jobs_done"]),
                          int(work.get("iterations") or 1))

"""What a DeepLearning job on enum and numeric columns needs, from the
deployment's shapes and whatever implements it. B is the minibatch, C and N
the enum and numeric columns, H1 .. Hm the hidden widths, K = 2 outputs.

A minibatch step (the training program's unit):

  FLOPs: the products of the forward pass and of the two backward ones
    (the activations' and the weights' gradients), a row of C + N non-zero
    inputs:
      step_flops = 6 * B * ((C + N) * H1 + H1 * H2 + ... + Hm * K)
  bytes: a parameter the step changes is read and written with its two
    ADADELTA accumulators, and its gradient is written: 28 B. The step
    changes every parameter after the first layer, the first layer's
    numeric rows and bias, and the rows of W1 its B rows touch: D * H1,
    with D the expected number of distinct levels B rows draw from each
    enum column under the configuration's level law (about 96.5 at B = 32 on
    the airline table). Add the batch's stored columns, response and
    weight:
      step_bytes = 28 * (D * H1 + (N + 1) * H1 + sum_l (H_l + 1) * H_{l+1})
                   + B * (stored_row_bytes + 1 + 4)

  1.7 MB and 2.1 us a step on a v5e for the airline network, bound by
  bytes; a step's 8 MFLOP take 0.04 us.

A whole-frame pass (the per-epoch loss and the training metrics): the
forward products of every row and one read of its stored columns, response
and weight:

  pass_flops = 2 * rows * ((C + N) * H1 + H1 * H2 + ... + Hm * K)
  pass_bytes = rows * (stored_row_bytes + 1 + 4)
"""

from __future__ import annotations

from bench.roofline.glm_irls_enum import stored_row_bytes

PASSES_A_JOB = 2            # the last epoch's loss and the training metrics


def _dims(cfg: dict):
    enums = [c for c in cfg["columns"] if c["type"] == "enum"]
    n_num = len(cfg["columns"]) - len(enums)
    return enums, n_num, [int(h) for h in cfg["params"]["hidden"]] + [2]


def distinct_levels(cfg: dict, batch: int) -> float:
    """Expected distinct levels ``batch`` rows draw, summed over the enum
    columns, under the recipe's level law (uniform where it has none)."""
    import numpy as np

    from bench.harness import data_airline

    law = data_airline.laws()["p"]
    total = 0.0
    for c in _dims(cfg)[0]:
        p = np.asarray(law.get(c["name"],
                               np.full(int(c["levels"]), 1.0 / int(c["levels"]))),
                       np.float64)
        total += float(np.sum(1.0 - (1.0 - p) ** batch))
    return total


def _products(cfg: dict) -> int:
    """Multiply-adds of one row's forward pass."""
    enums, n_num, widths = _dims(cfg)
    nz = len(enums) + n_num
    return nz * widths[0] + sum(a * b for a, b in zip(widths[:-1],
                                                      widths[1:]))


def step_need(cfg: dict) -> dict:
    batch = int(cfg["params"]["mini_batch_size"])
    _enums, n_num, widths = _dims(cfg)
    changed = distinct_levels(cfg, batch) * widths[0] \
        + (n_num + 1) * widths[0] \
        + sum((a + 1) * b for a, b in zip(widths[:-1], widths[1:]))
    return {"flops": float(6 * batch * _products(cfg)),
            "bytes": float(28 * changed
                           + batch * (stored_row_bytes(cfg) + 1 + 4))}


def pass_need(cfg: dict, rows: int) -> dict:
    return {"flops": float(2 * rows * _products(cfg)),
            "bytes": float(rows * (stored_row_bytes(cfg) + 1 + 4))}


def steps_a_job(cfg: dict, rows: int) -> int:
    p = cfg["params"]
    return int(round(float(p["epochs"]) * rows / int(p["mini_batch_size"])))


def program_needed(cfg: dict, rows: int, runs: int,
                   steps_a_run: float) -> dict:
    """``runs`` executions of the training program of ``steps_a_run``
    minibatch steps each (a run takes at most the program's steps a
    dispatch, so a job is many runs)."""
    return {k: v * runs * steps_a_run for k, v in step_need(cfg).items()}


def _counted(work: dict, name: str):
    made = (work.get("counters") or {}).get(name)
    return sum(made.values()) if made else None


def window_steps(cfg: dict, rows: int, work: dict) -> int:
    """Steps the window's jobs took: the program's counter where it has one,
    else a job's steps for each job."""
    made = _counted(work, "h2o3_dl_steps_total")
    if made:
        return int(made)
    return int(work["jobs_done"]) * steps_a_job(cfg, rows)


def steps_a_run(work: dict):
    """Mean steps a run of the training program took over the window, from
    the program's counters of steps and of runs; None without them."""
    steps = _counted(work, "h2o3_dl_steps_total")
    runs = _counted(work, "h2o3_dl_dispatches_total")
    return steps / runs if steps and runs else None


def step_needed(cfg: dict, rows: int, work: dict) -> dict:
    """All the jobs a window finished: their steps and their whole-frame
    passes."""
    steps = window_steps(cfg, rows, work)
    passes = pass_need(cfg, rows)
    jobs = int(work["jobs_done"]) * PASSES_A_JOB
    return {k: steps * v + jobs * passes[k]
            for k, v in step_need(cfg).items()}

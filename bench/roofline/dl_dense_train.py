"""What a DeepLearning job on an all-numeric frame needs, from the
deployment's shapes and whatever implements it. B is the minibatch, F the
design's live inputs (the configuration's ``live_inputs``: the pixels left
after the constant ones), H1 .. Hm the hidden widths, K the classes, P the
stored columns (the configuration's ``pixels``, 4 B each).

A minibatch step (the training program's unit):

  FLOPs: the products of the forward pass and of the two backward ones
    (the activations' and the weights' gradients), every input non-zero:
      step_flops = 6 * B * (F * H1 + H1 * H2 + ... + Hm * K)
  bytes: every parameter is read and written with its two ADADELTA
    accumulators, and its gradient is written: 28 B a parameter. Add the
    batch's stored rows, response and weight:
      step_bytes = 28 * sum_l (fan_in_l + 1) * fan_out_l + B * (4 P + 1 + 4)

  0.75 GFLOP and 109.4 MB a step for the MNIST network (F = 717, layers
  1024 / 1024 / 2048, K = 10): 3.8 us of FLOPs and 134 us of bytes on a
  v5e, bound by bytes.

A whole-frame pass (the per-epoch loss and the training metrics): the
forward products of every row and one read of its stored columns, response
and weight:

  pass_flops = 2 * rows * (F * H1 + H1 * H2 + ... + Hm * K)
  pass_bytes = rows * (4 P + 1 + 4)

  15.8 TFLOP and 6.36 GB at 2,025,000 rows: 80 ms at the bf16 peak, bound
  by FLOPs.
"""

from __future__ import annotations

from bench.roofline import dl_train

PASSES_A_JOB = dl_train.PASSES_A_JOB


def _dims(cfg: dict) -> list:
    return [int(cfg["live_inputs"])] \
        + [int(h) for h in cfg["params"]["hidden"]] + [int(cfg["classes"])]


def stored_row_bytes(cfg: dict) -> int:
    return 4 * int(cfg["pixels"]) + 1 + 4


def parameters(cfg: dict) -> int:
    d = _dims(cfg)
    return sum((a + 1) * b for a, b in zip(d[:-1], d[1:]))


def _products(cfg: dict) -> int:
    """Multiply-adds of one row's forward pass."""
    d = _dims(cfg)
    return sum(a * b for a, b in zip(d[:-1], d[1:]))


def step_need(cfg: dict) -> dict:
    batch = int(cfg["params"]["mini_batch_size"])
    return {"flops": float(6 * batch * _products(cfg)),
            "bytes": float(28 * parameters(cfg)
                           + batch * stored_row_bytes(cfg))}


def pass_need(cfg: dict, rows: int) -> dict:
    return {"flops": float(2 * rows * _products(cfg)),
            "bytes": float(rows * stored_row_bytes(cfg))}


def program_needed(cfg: dict, rows: int, runs: int,
                   steps_a_run: float) -> dict:
    """``runs`` executions of the training program of ``steps_a_run``
    minibatch steps each."""
    return {k: v * runs * steps_a_run for k, v in step_need(cfg).items()}


def pass_needed(cfg: dict, rows: int, runs: int) -> dict:
    """``runs`` whole-frame passes over ``rows`` rows."""
    return {k: v * runs for k, v in pass_need(cfg, rows).items()}


def step_needed(cfg: dict, rows: int, work: dict) -> dict:
    """All the jobs a window finished: their steps and their whole-frame
    passes (steps from the program's counter, else from the epochs)."""
    steps = dl_train.window_steps(cfg, rows, work)
    passes = pass_need(cfg, rows)
    jobs = int(work["jobs_done"]) * PASSES_A_JOB
    return {k: steps * v + jobs * passes[k]
            for k, v in step_need(cfg).items()}

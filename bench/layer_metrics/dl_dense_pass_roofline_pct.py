"""Roofline share of the whole-frame pass programs of a DeepLearning job
on an all-numeric frame (the per-epoch loss and the training metrics'
predictions; bench/roofline/dl_dense_train.pass_need): the least time of
the passes that lie wholly inside the traced slice over their device
time. A pass the slice cuts is left out. The configuration's
``programs.dl_dense_pass`` names the programs, and the cell's driver reads
their runs from the device trace (bench/harness/trace_runs); None where
the slice holds no whole pass."""

from bench.roofline import dl_dense_train, peaks


def read(run, name):
    if not run.trace or run.peak is None:
        return None
    found = (run.window.get("slice_programs") or {}).get("dl_dense_pass")
    if not found or found["whole_runs"] <= 0 or found["whole_seconds"] <= 0:
        return None
    need = dl_dense_train.pass_needed(run.cfg, run.rows,
                                      found["whole_runs"])
    return 100.0 * peaks.least_seconds(need, run.peak) \
        / found["whole_seconds"]

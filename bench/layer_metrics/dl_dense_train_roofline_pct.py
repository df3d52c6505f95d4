"""Roofline share of the DeepLearning training program on an all-numeric
frame: the least time of the minibatch steps the traced slice holds
(bench/roofline/dl_dense_train.step_need) over the device seconds the
program spent in the slice.

Both numbers come from the device trace (bench/harness/trace_runs, read
by the cell's driver while the slice is on disk): the seconds are the
program's runs inside the slice, and the steps are its loop's iterations
there, counted by the operations of the loop's body. A run is up to 2,048
steps of about a millisecond, so the slice cuts runs at both of its ends,
and the count of runs says nothing of the steps. None without the slice's
reading of the program (``programs.dl_dense_train``)."""

from bench.roofline import dl_dense_train, peaks


def read(run, name):
    if not run.trace or run.peak is None:
        return None
    found = (run.window.get("slice_programs") or {}).get("dl_dense_train")
    if not found or found["iterations"] <= 0 or found["seconds"] <= 0:
        return None
    need = {k: v * found["iterations"]
            for k, v in dl_dense_train.step_need(run.cfg).items()}
    return 100.0 * peaks.least_seconds(need, run.peak) / found["seconds"]

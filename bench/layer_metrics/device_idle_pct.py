"""Share of the traced slice in which no operation ran on the device:
1 - (union of the device-op intervals) / slice, averaged over the chips."""


def read(run, name):
    t = run.trace
    if not t or not t.get("n_devices") or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

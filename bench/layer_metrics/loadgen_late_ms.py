"""99th percentile of (actual send - due): how late the generator ran."""


def read(run, name):
    return run.window.get("late_p99_ms")

"""Client-side median of the window's requests, from the due time."""


def read(run, name):
    return run.window.get("p50_ms")

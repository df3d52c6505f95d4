"""Programs compiled inside the measured window: JAX's backend-compile events
less the ones its persistent cache served (should be 0)."""


def read(run, name):
    return run.window_compiles

"""window_compiles: backend compiles inside the measured window, from
JAX's monitoring events (executor)."""


def read(run):
    return run.window_compiles

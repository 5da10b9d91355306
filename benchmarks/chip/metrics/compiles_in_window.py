"""compiles_in_window: programs made ready (compiled, or read from the
persistent cache) while the window ran, from JAX's compile events."""


def read(run):
    return run.compiles_in_window

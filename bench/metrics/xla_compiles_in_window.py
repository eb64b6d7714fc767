"""XLA compile requests inside the window (JAX's backend-compile events,
persistent-cache hits included): warm-up should leave none."""


def read(run):
    return run.compiles_in_window

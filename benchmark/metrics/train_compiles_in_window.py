"""Programs XLA compiled, or fetched from the persistent cache, between the
window's start and its end (``jax.monitoring`` backend-compile events).
Anything but 0 means warm-up missed a shape."""

TIMING = False


def read(run):
    return run["compiles_in_window"]

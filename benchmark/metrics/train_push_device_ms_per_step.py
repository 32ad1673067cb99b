"""Device milliseconds per step under the program's ``push_a2a`` (and
``hash_push_a2a``), from the device trace."""

from ._common import scope_ms_per_step

TIMING = True


def read(run):
    return scope_ms_per_step(run, "push_a2a")

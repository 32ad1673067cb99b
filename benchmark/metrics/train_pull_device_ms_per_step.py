"""Device milliseconds per step under the program's ``pull_a2a`` (and
``hash_pull_a2a``), from the device trace."""

from ._common import scope_ms_per_step

TIMING = True


def read(run):
    return scope_ms_per_step(run, "pull_a2a")

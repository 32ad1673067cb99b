"""Device milliseconds per step in the program's ``dedup`` stage (unique ids
and the combining of their gradients, at the sender and at the owner), from
the device trace: ``stage_reduce``."""

from ..stage_reduce import stage_ms_per_step

TIMING = True


def read(run):
    return stage_ms_per_step(run, "dedup")

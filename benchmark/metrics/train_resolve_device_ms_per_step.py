"""Device milliseconds per step in the program's ``resolve`` stage (the
owner's row read of a pull), from the device trace: ``stage_reduce``."""

from ..stage_reduce import stage_ms_per_step

TIMING = True


def read(run):
    return stage_ms_per_step(run, "resolve")

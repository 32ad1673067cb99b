"""Device milliseconds per step in the program's ``apply_gather``,
``apply_update`` and ``apply_scatter`` stages (the sparse optimizer step at
the owner), from the device trace: ``stage_reduce``."""

from ..stage_reduce import stage_ms_per_step

TIMING = True


def read(run):
    return stage_ms_per_step(run, "apply_gather", "apply_update", "apply_scatter")

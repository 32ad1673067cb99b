"""Device milliseconds per step in the program's ``route`` and ``expand``
stages (owners, buckets, un-bucketing; unique rows back to one row per
index), from the device trace: ``stage_reduce``."""

from ..stage_reduce import stage_ms_per_step

TIMING = True


def read(run):
    return stage_ms_per_step(run, "route", "expand")

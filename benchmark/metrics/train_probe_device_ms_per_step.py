"""Device milliseconds per step in the program's ``probe`` and ``init_rows``
stages (hash find, find-or-insert, and the drawing of fresh rows), from the
device trace: ``stage_reduce``. An array table has neither."""

from ..stage_reduce import stage_ms_per_step

TIMING = True


def read(run):
    return stage_ms_per_step(run, "probe", "init_rows")

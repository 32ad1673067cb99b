"""Device milliseconds per step in the program's ``dense_fwd``, ``dense_bwd``
and ``dense_update`` stages (the model and its optimizer), from the device
trace: ``stage_reduce``."""

from ..stage_reduce import stage_ms_per_step

TIMING = True


def read(run):
    return stage_ms_per_step(run, "dense_fwd", "dense_bwd", "dense_update")

"""Device milliseconds per step in the tier's insert program (stage
``offload_insert``: the find-or-insert of a step's missing keys and the
scatter of their rows and accumulators), both tables, from the device
trace."""

from ._offload import insert_device_ms_per_step

TIMING = True


def read(run):
    return insert_device_ms_per_step(run)

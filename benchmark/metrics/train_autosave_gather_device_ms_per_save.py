"""Device milliseconds per save in the snapshot's programs (stage
``ckpt_gather``: the gather of the dirty rows of both tables into staging
buffers, and the copy of the dense state), from the device trace's ``XLA
Modules`` line."""

from ._autosave import gather_device_s

TIMING = True


def read(run):
    found = gather_device_s(run)
    return found and found[0] * 1e3 / found[1]

"""Device milliseconds per save under the snapshot programs' ``ckpt_find``
stage: the find of the dirty keys' slots, both tables, ahead of the gather
in the same programs (``_autosave_keys.find_device_s``). None where the
snapshot has no find."""

from ._autosave_keys import find_device_s

TIMING = True


def read(run):
    found = find_device_s(run)
    return found and found[0] * 1e3 / found[1]

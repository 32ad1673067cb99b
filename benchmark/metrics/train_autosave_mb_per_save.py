"""Megabytes a save wrote (counter ``ckpt_delta_bytes``: the delta files
of both tables, ids and rows)."""

from ._autosave import counter_per_save

TIMING = False


def read(run):
    value = counter_per_save(run, "ckpt_delta_bytes")
    return value and value / 1e6

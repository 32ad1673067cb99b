"""Rows a save carried, a table: the rows pushed since the snapshot before,
and no others. Counter ``ckpt_delta_rows`` sums the save's tables; the
configuration's two (dim k and dim 1) are pushed the same ids."""

from ._autosave import TABLES, counter_per_save

TIMING = False


def read(run):
    value = counter_per_save(run, "ckpt_delta_rows")
    return value and value / TABLES

"""Milliseconds from a snapshot's dispatch to its manifest rename, mean
over the window's saves: how long a finished step is not yet durable."""

from ._autosave import span_ms_per_save

TIMING = True


def read(run):
    return span_ms_per_save(run, "ckpt_commit_lag_s")

"""Milliseconds per save in ``ckpt.d2h`` on the writer thread: from its
start to the staged rows on the host: the wait for the steps dispatched
ahead of the gather, the gather, and the copy."""

from ._autosave import span_ms_per_save

TIMING = True


def read(run):
    return span_ms_per_save(run, "ckpt.d2h")

"""Seconds of set-up tracing functions and lowering them to modules: Python,
paid warm or cold, once a program."""

from ._setup import total

TIMING = True


def read(run):
    return total(run, "trace_s", "lower_s")

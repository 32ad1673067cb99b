"""Seconds of set-up inside the backend events of programs XLA compiled.
0.0 on a warm run."""

from ._setup import total

TIMING = True


def read(run):
    return total(run, "compile_s")

"""Seconds of set-up inside the backend events of programs the persistent
cache held: reading and deserialising executables."""

from ._setup import total

TIMING = True


def read(run):
    return total(run, "fetch_s")

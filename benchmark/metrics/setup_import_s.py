"""Seconds from the operating system's start of the process to the install
of the program's load ledger at the package's import: the interpreter,
``import jax``, the package. Reaching the chip comes after and is in the
residue of ``setup_s``."""

from ._setup import ledger

TIMING = True


def read(run):
    read = ledger(run)
    return read and read["import_s"]

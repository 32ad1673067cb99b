"""Seconds of set-up inside the calls of ``Trainer.fit`` that returned before
the window's call began: the followed steps and the warm-up, their fetches
or compiles included."""

from ._setup import ledger

TIMING = True


def read(run):
    read = ledger(run)
    return read and read["warm_fit_s"]

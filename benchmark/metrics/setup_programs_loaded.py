"""Programs XLA compiled, or fetched from the persistent cache, before the
window's call of ``Trainer.fit`` began: the load ledger's backend events, the
harness's fills and saves among them."""

from ._setup import total

TIMING = False


def read(run):
    return total(run, "programs")

"""Of ``setup_programs_loaded``, the programs XLA compiled: the persistent
cache did not hold them (``miss``) or was not asked (``off``). 0 on a warm
run."""

from ._setup import total

TIMING = False


def read(run):
    return total(run, "misses", "off")

"""Rows fetched from the host store per step, both tables together
(counter ``offload_miss_rows``): the distinct ids of a batch that the
cache did not hold."""

from ._offload import counter_per_step

TIMING = False


def read(run):
    return counter_per_step(run, "offload_miss_rows")

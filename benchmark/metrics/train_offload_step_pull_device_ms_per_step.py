"""Device milliseconds per step under the step program's ``pull_a2a``
(``hash_pull_a2a``): the cache's find and row read of a pull,
from the device events inside the step program's executions alone
(``_offload.step_stages``): the tier's insert program runs beside the
step and its events carry the same instruction names."""

from ._offload import step_scope_ms_per_step

TIMING = True


def read(run):
    return step_scope_ms_per_step(run, "pull_a2a")

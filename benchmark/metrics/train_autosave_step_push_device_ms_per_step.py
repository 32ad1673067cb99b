"""Device milliseconds per step under the step program's ``push_a2a``,
from the device events inside the step program's executions alone
(``_offload.step_stages``): the snapshot's gather programs run beside the
step and their events carry the step's instruction names."""

from ._offload import step_scope_ms_per_step

TIMING = True


def read(run):
    return step_scope_ms_per_step(run, "push_a2a")

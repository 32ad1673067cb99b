"""Device milliseconds per step in the step program's ``dense_fwd``,
``dense_bwd`` and ``dense_update`` stages,
from the device events inside the step program's executions alone
(``_offload.step_stages``): the snapshot's gather programs run beside the
step and their events carry the step's instruction names."""

from ._offload import step_stage_ms_per_step

TIMING = True


def read(run):
    return step_stage_ms_per_step(
        run, "dense_fwd", "dense_bwd", "dense_update")

"""Device milliseconds per step in the step program's ``probe`` and
``init_rows`` stages (the pull's find, the pushes' find and insert, the
drawing of fresh rows), from the device events inside the step program's
executions alone (``_offload.step_stages``): the snapshot programs run
beside the step, their events carry the step's instruction names, and
their own find is not the step's."""

from ._offload import step_stage_ms_per_step

TIMING = True


def read(run):
    return step_stage_ms_per_step(run, "probe", "init_rows")

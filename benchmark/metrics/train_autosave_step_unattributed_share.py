"""Share of the step program's busy time in operations that no stage
names, from the device events inside the step program's executions alone
(``_offload.step_stages``)."""

from ..stage_reduce import UNATTRIBUTED
from ._offload import step_stages

TIMING = True


def read(run):
    table = step_stages(run)
    if not table or not table["busy_s"]:
        return None
    return 100.0 * table["stage_s"].get(UNATTRIBUTED, 0.0) / table["busy_s"]

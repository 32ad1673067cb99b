"""Share of the device's busy time in operations that no stage of the
program names, from the device trace: ``stage_reduce``."""

from ..stage_reduce import UNATTRIBUTED, reduce_run

TIMING = True


def read(run):
    table = reduce_run(run)
    if not table or not table["busy_s"]:
        return None
    return 100.0 * table["stage_s"].get(UNATTRIBUTED, 0.0) / table["busy_s"]

"""Share of the HBM roofline the gather kernels reach: the bytes the
pulled and re-read rows need, over peak bandwidth, over gather device
time. Memory-bound: a gather does no arithmetic."""

from .. import counts
from ._common import peaks, unique_rows

TIMING = True


def read(run):
    trace = run.get("trace")
    if not trace or not trace["steps"] or not trace["kind_s"].get("gather"):
        return None
    unique = unique_rows(run)
    need_s = counts.gather_bytes(run["config"], unique) * trace["steps"] \
        / run["config"]["chips"] / peaks(run)["hbm_bytes_per_s"]
    return 100.0 * need_s / trace["kind_s"]["gather"]

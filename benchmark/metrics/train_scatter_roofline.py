"""Share of the HBM roofline the scatter kernels reach: the bytes the row
updates write (weights and Adagrad accumulator), over peak bandwidth, over
scatter device time. Memory-bound."""

from .. import counts
from ._common import peaks, unique_rows

TIMING = True


def read(run):
    trace = run.get("trace")
    if not trace or not trace["steps"] or not trace["kind_s"].get("scatter"):
        return None
    unique = unique_rows(run)
    need_s = counts.scatter_bytes(run["config"], unique) * trace["steps"] \
        / run["config"]["chips"] / peaks(run)["hbm_bytes_per_s"]
    return 100.0 * need_s / trace["kind_s"]["scatter"]

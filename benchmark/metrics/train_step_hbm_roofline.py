"""The whole step's share of the chips' HBM bandwidth: the bytes one step
must move for its rows (each distinct row pulled once, then read and
written with its accumulator), counted from the window's own batches, over
peak bandwidth, over the traced step time."""

from .. import counts
from ._common import peaks, traced_step_s, unique_rows

TIMING = True


def read(run):
    step_s = traced_step_s(run)
    if step_s is None:
        return None
    config = run["config"]
    unique = unique_rows(run)
    need_s = counts.step_hbm_bytes(config, unique) \
        / (config["chips"] * peaks(run)["hbm_bytes_per_s"])
    return 100.0 * need_s / step_s

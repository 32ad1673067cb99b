"""Share of the HBM roofline the snapshot's gather reaches: the logical
bytes of the rows a save carries (``counts_chain.gather_bytes``), over peak
bandwidth, over the gather programs' device time."""

from .. import counts_chain
from ._autosave import TABLES, counter_per_save, gather_device_s
from ._common import peaks

TIMING = True


def read(run):
    found = gather_device_s(run)
    rows = counter_per_save(run, "ckpt_delta_rows")
    if not found or not rows:
        return None
    need_s = counts_chain.gather_bytes(run["config"], rows / TABLES) \
        / peaks(run)["hbm_bytes_per_s"]
    return 100.0 * need_s * found[1] / found[0]

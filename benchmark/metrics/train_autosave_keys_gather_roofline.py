"""Share of the HBM roofline a hash table's snapshot reaches: the logical
bytes a save has to read (``counts_chain_keys.gather_bytes``: the key, the
row and the accumulator of every dirty key of both tables, once), over
peak bandwidth, over the ``ckpt_gather`` programs' device time, their find
included."""

from .. import counts_chain_keys
from ._autosave import TABLES, counter_per_save, gather_device_s
from ._common import peaks

TIMING = True


def read(run):
    found = gather_device_s(run)
    keys = counter_per_save(run, "ckpt_delta_rows")
    if not found or not keys:
        return None
    need_s = counts_chain_keys.gather_bytes(run["config"], keys / TABLES) \
        / peaks(run)["hbm_bytes_per_s"]
    return 100.0 * need_s * found[1] / found[0]

"""Share of the HBM roofline the wide-key insert between the steps
reaches: the logical bytes of the rows it copied in
(``counts_offload_keys.insert_bytes``: key, weight row and accumulator of
the rows a step of the window fetched from the store) over peak bandwidth,
over the insert program's device time a traced step on the trace's ``XLA
Modules`` line (the accepted reader's,
``train_offload_insert_device_ms_per_step``)."""

from .. import counts_offload_keys
from ._common import peaks
from ._offload import insert_device_ms_per_step
from ._offload_keys import counter

TIMING = True


def read(run):
    device_ms = insert_device_ms_per_step(run)
    rows = counter(run, "offload_miss_rows")
    if not device_ms or not rows or not run["steps"]:
        return None
    need_s = counts_offload_keys.insert_bytes(
        run["config"], rows / run["steps"]) / peaks(run)["hbm_bytes_per_s"]
    return 100.0 * need_s / (device_ms * 1e-3)

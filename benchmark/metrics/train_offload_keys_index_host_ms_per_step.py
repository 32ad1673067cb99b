"""Host milliseconds per step in ``offload.key_index``, both tables, on
whichever thread prepares (the lookahead thread, in a window): the find
and insert of a step's distinct keys in the host index, a part of
``train_offload_prepare_host_ms_per_step``."""

from ._offload import per_step_ms, span_s

TIMING = True


def read(run):
    return per_step_ms(run, span_s(run, "offload.key_index"))

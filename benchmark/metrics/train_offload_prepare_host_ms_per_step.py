"""Host milliseconds per step in ``offload.host_prepare``, both tables,
on the lookahead thread: the batch's unique ids, the residency books, and
the gather of the missing rows and accumulators from the host store.
Hidden behind the device's step unless ``train_offload_wait_ms_per_step``
says otherwise."""

from ._offload import per_step_ms, span_s

TIMING = True


def read(run):
    return per_step_ms(run, span_s(run, "offload.host_prepare"))

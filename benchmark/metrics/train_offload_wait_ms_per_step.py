"""Host milliseconds per step in ``offload.wait_prepare``: the step loop
blocked on the lookahead thread, the part of the tier's host time that is
exposed."""

from ._offload import per_step_ms, span_s

TIMING = True


def read(run):
    return per_step_ms(run, span_s(run, "offload.wait_prepare"))

"""Host milliseconds per step in the trainer's ``trainer.dispatch`` span (the
call of the jitted step), over the steps that begin inside the traced device
window: ``stage_reduce``."""

from ..stage_reduce import host_ms_per_step

TIMING = True


def read(run):
    return host_ms_per_step(run, "trainer.dispatch")

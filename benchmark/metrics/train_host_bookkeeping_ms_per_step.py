"""Host milliseconds per step in the trainer's ``trainer.bookkeeping`` span
(batch statistics, dirty marks, offload and hot-cache notes, on both sides
of the dispatch), over the steps that begin inside the traced device window:
``stage_reduce``."""

from ..stage_reduce import host_ms_per_step

TIMING = True


def read(run):
    return host_ms_per_step(run, "trainer.bookkeeping")

"""Host milliseconds per step of the tier's own work on the step thread,
both tables: ``offload.apply_prepared`` (the books' planned-to-resident
move, the missing rows packed into one buffer a table, its copy to the
device, the call of the insert program) less ``offload.insert_dispatch``,
the call itself, which returns only when the runtime has room for one more
launched program and so reads a device step, not host work."""

from ._offload import per_step_ms, span_s

TIMING = True


def read(run):
    whole = span_s(run, "offload.apply_prepared")
    call = span_s(run, "offload.insert_dispatch")
    if whole is None or call is None:
        return None
    return per_step_ms(run, whole - call)

"""Host milliseconds per save in ``trainer.autosave``, on the step thread:
the wait for the save before (at most one is in flight), the dirty sets'
snapshot, the dispatch of the gather. What a save costs the step loop."""

from ._autosave import span_ms_per_save

TIMING = True


def read(run):
    return span_ms_per_save(run, "trainer.autosave")

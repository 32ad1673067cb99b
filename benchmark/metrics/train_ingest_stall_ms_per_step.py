"""Milliseconds per step that ``Trainer.fit`` blocked on its batch source
(``observability.record_ingest_stall``), less the time the benchmark's own
feed held it back to bound the steps in flight."""

TIMING = True


def read(run):
    if not run["steps"]:
        return None
    return max(run["ingest_stall_s"], 0.0) * 1e3 / run["steps"]

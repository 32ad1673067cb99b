"""Host milliseconds per step inside ``Trainer.fit``: the span around
``fit`` less the time its feed waited for the device. What is left is the
host's own work: placing the batch, dispatching the step, bookkeeping."""

TIMING = True


def read(run):
    if not run["steps"]:
        return None
    return (run["window_s"] - run["waited_s"]) * 1e3 / run["steps"]

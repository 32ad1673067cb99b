"""Device milliseconds per step in collectives (all-to-all, all-gather,
all-reduce, collective-permute), per chip, from the device trace. One chip
runs none, and the reader returns nothing there."""

TIMING = True


def read(run):
    trace = run.get("trace")
    if not trace or not trace["steps"] \
            or "collective" not in trace["kind_s"]:
        return None
    return trace["kind_s"]["collective"] * 1e3 / trace["steps"]

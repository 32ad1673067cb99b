"""Keys a step brings that no store has seen, per table (counter
``offload_fresh_keys``: keys handed a store row, both tables together):
each is born in the step and owed a write-back."""

from ._offload_keys import counter

TIMING = False
TABLES = 2


def read(run):
    fresh = counter(run, "offload_fresh_keys")
    if fresh is None or not run["steps"]:
        return None
    return fresh / TABLES / run["steps"]

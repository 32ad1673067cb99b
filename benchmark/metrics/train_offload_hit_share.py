"""Share of the distinct rows a step needs that the HBM cache already
held (counters ``offload_miss_rows`` over ``offload_unique_rows``, the
whole window, both tables)."""

TIMING = False


def read(run):
    tier = run.get("offload")
    if not tier or not tier.get("offload_unique_rows"):
        return None
    return 100.0 * (1.0 - tier["offload_miss_rows"]
                    / tier["offload_unique_rows"])

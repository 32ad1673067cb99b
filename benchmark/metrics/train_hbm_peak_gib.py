"""Peak device memory, fullest chip (``memory_stats()``)."""

TIMING = True


def read(run):
    if not run["memory_peak_bytes"]:
        return None
    return run["memory_peak_bytes"] / 2 ** 30

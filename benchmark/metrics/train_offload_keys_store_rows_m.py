"""Rows a table's keyed store has handed out by the window's end, in
millions (the tier's gauge ``store_rows``, the larger table's): beside
the configuration's ``store_keys_at_start`` it says the store grew, and
by how much."""

from ._offload_keys import store_gauge

TIMING = False


def read(run):
    rows = store_gauge(run, "store_rows")
    return None if rows is None else max(rows.values()) / 1e6

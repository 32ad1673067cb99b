"""Bytes the wide-key insert between two steps has to move: functions of
the configuration and of how many rows the tier copied from the store,
never of how the program lays rows out."""

from . import counts

KEY_BYTES = 8


def inserted_row_bytes(config):
    """Logical bytes of one row copied from the store into the cache,
    both tables: the key, the weight row and its Adagrad accumulator, the
    dim-k table's and the dim-1 table's."""
    return 2 * KEY_BYTES + 2 * counts.row_bytes(config)


def insert_bytes(config, miss_rows):
    """Bytes the insert programs have to write for ``miss_rows`` rows
    copied from the store (the counter ``offload_miss_rows`` counts a row
    a table, so a key missed in both tables counts twice)."""
    return miss_rows * inserted_row_bytes(config) / 2

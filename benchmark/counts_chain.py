"""Bytes and rows a delta save needs: functions of the configuration and
the traffic only, as ``counts.py`` for the step."""

import numpy as np

from . import counts

ID_BYTES = 8


def saved_row_bytes(config):
    """Logical bytes of one dirty row across both tables: the row and its
    Adagrad accumulator, dim-k and dim-1."""
    return 2 * counts.row_bytes(config)


def distinct_rows(raw_batches):
    """Distinct (feature, id) pairs over a run of batches: the rows of one
    table that a save following them has to carry."""
    if not raw_batches:
        return 0
    features = raw_batches[0]["ids"].shape[1]
    return int(sum(np.unique(np.concatenate(
        [b["ids"][:, j] for b in raw_batches])).size
        for j in range(features)))


def gather_bytes(config, rows):
    """Bytes a snapshot's gather has to read: every dirty row of one table
    and its accumulator, once (``rows`` counts one table's rows; the
    linear table has the same ids)."""
    return rows * saved_row_bytes(config)

"""Bytes and keys a delta save of the hash tables needs: functions of the
configuration and the traffic only, as ``counts_chain.py`` for the array
tables."""

import numpy as np

from . import counts

KEY_BYTES = 8


def saved_key_bytes(config):
    """Logical bytes of one dirty key across both tables: the key, the row
    and its Adagrad accumulator, dim-k and dim-1."""
    return 2 * (KEY_BYTES + counts.row_bytes(config))


def gather_bytes(config, keys):
    """Bytes a snapshot has to read: the key, the row and the accumulator
    of every dirty key of both tables, once (``keys`` counts one table's;
    the linear table has the same keys)."""
    return keys * saved_key_bytes(config)


def distinct_keys(raw_batches, held=None):
    """Distinct (feature, key) pairs over a run of batches: the keys of
    one table that a save following them has to carry. ``held(feature,
    ids, ranks)`` tells which of a feature's keys the table holds (a save
    carries no row of a key that was marked and never pushed); without it
    every key counts."""
    if not raw_batches:
        return 0
    total = 0
    for j in range(raw_batches[0]["ids"].shape[1]):
        ids = np.concatenate([b["ids"][:, j] for b in raw_batches])
        ids, first = np.unique(ids, return_index=True)
        if held is not None:
            ranks = np.concatenate(
                [b["ranks"][:, j] for b in raw_batches])[first]
            ids = ids[held(j, ids, ranks)]
        total += int(ids.size)
    return total


def held_after(config, trained):
    """``held`` for a table that was filled with ranks 1..K of every
    feature (``prefill_ranks_per_feature``) and then trained on the raw
    batches ``trained``."""
    top = config["prefill_ranks_per_feature"]
    pushed = [np.unique(np.concatenate([b["ids"][:, j] for b in trained]))
              for j in range(trained[0]["ids"].shape[1])] if trained else None

    def held(feature, ids, ranks):
        there = ranks <= top
        if pushed is not None:
            there |= np.isin(ids, pushed[feature])
        return there

    return held

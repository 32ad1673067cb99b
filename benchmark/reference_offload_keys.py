"""The plain reference for a table of unbounded keys behind the offload
tier, and what its store has to hold.

The step is ``reference.py``'s, imported: it does not care where rows
live. What this file adds is which keys are which at the start. A key is
STORED when the configuration says the job has met it: its Zipf rank is
within ``store_keys_at_start`` (ranks 1..K of every feature), or it is
one of the rarer keys of the traffic's pool and a seeded hash of the key
falls under ``seen_share_of_tail``. A stored key starts from its seeded
row (``seeded.table_rows``); any other key is FRESH and starts from the
row the configuration's ``fresh_rows`` rule gives it, per key. After the
steps and a flush the store has to hold, under its key, the row of every
key a step has touched, fresh keys included.
"""

import numpy as np

from . import reference, seeded

TAG_SEEN = 0x7365656E       # which rare keys a job late in its epoch met


def store_ranks(config):
    """K: the store holds ranks 1..K of every feature at the start."""
    return config["store_keys_at_start"] // config["sparse_features"]


def stored(seed, config, feature, ids, ranks):
    """Mask of the (feature, id) pairs the store holds at the start."""
    lo, hi = seeded.split_words(ids)
    met = seeded.hash_u32(seed, TAG_SEEN, (feature, lo, hi)) \
        < np.uint32(min(config["seen_share_of_tail"], 1.0) * 0xFFFFFFFF)
    return (ranks <= store_ranks(config)) | met


def seen_tail(seed, config, raw_pool):
    """(feature [N], id [N]) of the pool's keys past the store's ranks
    that the store holds all the same: distinct, a feature at a time."""
    top = store_ranks(config)
    feats, keys = [], []
    for j in range(config["sparse_features"]):
        ids = np.concatenate([b["ids"][:, j] for b in raw_pool])
        ranks = np.concatenate([b["ranks"][:, j] for b in raw_pool])
        ids, first = np.unique(ids[ranks > top], return_index=True)
        f = np.full(len(ids), j, np.int64)
        met = stored(seed, config, f, ids, ranks[ranks > top][first])
        feats.append(f[met])
        keys.append(ids[met])
    return np.concatenate(feats), np.concatenate(keys)


def _as_hash(seed, config, batches):
    """``batches`` and ``config`` as ``reference.py`` reads a hash table
    that holds exactly the stored keys: a key's rank is 1 where the store
    holds it and 2 where it is fresh, and the table was filled with the
    ranks up to 1."""
    out = []
    for b in batches:
        feature = np.broadcast_to(
            np.arange(b["ids"].shape[1], dtype=np.int64), b["ids"].shape)
        held = stored(seed, config, feature, b["ids"], b["ranks"])
        out.append(dict(b, ranks=np.where(held, 1, 2)))
    return dict(config, table_kind="hash", prefill_ranks_per_feature=1), out


def follow(seed, config, batches, **kw):
    """``reference.follow`` over a store that holds what :func:`stored`
    says and meets every other key fresh."""
    as_config, as_batches = _as_hash(seed, config, batches)
    return reference.follow(seed, as_config, as_batches, **kw)


def start_rows(seed, config, batches):
    """({table: [n, B, F, dim]}, stored [n, B, F]): the row every lookup
    of ``batches`` starts from (the store's seeded row, or a fresh key's
    own first row) and whether the store holds its key at the start."""
    as_config, as_batches = _as_hash(seed, config, batches)
    feature, ids, index, ranks, _ = reference.compact_ids(as_batches)
    tables = reference.initial_tables(seed, as_config, feature, ids, ranks)
    return ({t: np.asarray(rows)[index] for t, rows in tables.items()},
            (ranks == 1)[index])

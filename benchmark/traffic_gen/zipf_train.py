"""Criteo-shaped training batches with Zipf id frequency.

A copy of the arithmetic of ``openembedding_tpu.data.criteo.
synthetic_criteo`` (kept here so that no later PR can change the traffic):
rank ``r ~ Zipf(a)`` per feature, decorated per feature and avalanche-mixed
into the id space. ``id_range`` bounds the ids (an array table's rows per
feature); without it the id is the 62-bit key of the reference's
``to_hash_bucket_fast(col, 2**62)``.
"""

import numpy as np

from .. import seeded

NUM_DENSE = 13


def feature_ids(ranks, feature, id_range):
    """Ids (uint64) of Zipf ranks for one feature column (0-based)."""
    mixed = seeded.mix64(ranks.astype(np.uint64) * np.uint64(feature + 1))
    if id_range:
        return mixed % np.uint64(id_range)
    return mixed & seeded.MASK62


def make(params, config, seed):
    """``params['pool_batches']`` distinct raw batches from the seed:
    ``ids`` uint64 [B, F] with the Zipf ``ranks`` they came from, ``dense``
    float32 [B, 13], ``label`` float32 [B]. Needs numpy alone, so the
    entry point draws it on a thread while JAX starts.
    """
    batch, features = config["batch"], config["sparse_features"]
    id_range = config.get("rows_per_feature")
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    n = int(params["pool_batches"])
    ranks = rng.zipf(float(params["zipf_a"]), size=(n, batch, features))
    ids = np.empty(ranks.shape, np.uint64)
    for j in range(features):
        ids[..., j] = feature_ids(ranks[..., j], j, id_range)
    dense = np.log1p(rng.poisson(3.0, size=(n, batch, NUM_DENSE))
                     .astype(np.float32))
    label = (rng.random((n, batch)) > 0.75).astype(np.float32)
    return [{"ids": ids[i], "ranks": ranks[i], "dense": dense[i],
             "label": label[i]}
            for i in range(n)]

"""Operations and bytes a step needs: functions of the configuration and
the traffic only (logical row widths, unique ids), never of how the program
lays rows out, so they read the same whatever later implements a kernel."""

import numpy as np

FLOAT_BYTES = 4


def row_bytes(config):
    """Bytes of one logical row across both tables: the dim-k embedding
    and the dim-1 first-order weight."""
    return (config["embedding_dim"] + config["linear_dim"]) * FLOAT_BYTES


def unique_rows(raw_batch):
    """Distinct (feature, id) pairs of one batch: the rows a step must
    pull once and update once."""
    ids = raw_batch["ids"]
    return int(sum(np.unique(ids[:, j]).size for j in range(ids.shape[1])))


def mean_unique_rows(raw_batches, sample=32):
    picked = raw_batches[:sample]
    return float(np.mean([unique_rows(b) for b in picked]))


def gather_bytes(config, unique):
    """Rows read per step: every distinct row once for the pull, and once
    more with its Adagrad accumulator for the update."""
    return unique * row_bytes(config) * 3


def scatter_bytes(config, unique):
    """Rows written per step: every distinct row and its accumulator."""
    return unique * row_bytes(config) * 2


def step_hbm_bytes(config, unique):
    """Bytes one step must move through HBM for its rows: pull each
    distinct row, then read and write it and its accumulator."""
    return unique * row_bytes(config) * (1 + 2 + 2)


def dense_flops_per_example(config):
    """Multiply-adds x2 of DeepFM's forward pass for one example, and the
    backward pass at twice that: the MLP over fields and dense columns, its
    head, and the FM second-order term."""
    width = config["sparse_features"] * config["embedding_dim"] \
        + config["dense_features"]
    sizes = [width, *config["dnn_units"], 1]
    mlp = sum(2 * a * b for a, b in zip(sizes, sizes[1:]))
    fm = 4 * config["sparse_features"] * config["embedding_dim"]
    return 3 * (mlp + fm)

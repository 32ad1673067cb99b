"""The byte and FLOP counts against hand-worked values at one tiny shape,
and the seeded generators against themselves on numpy and jax.numpy."""

import numpy as np
import pytest

from benchmark import counts, seeded

TINY = {"sparse_features": 3, "dense_features": 2, "embedding_dim": 4,
        "linear_dim": 1, "dnn_units": [8, 5], "chips": 1}


def test_row_bytes():
    assert counts.row_bytes(TINY) == (4 + 1) * 4


def test_unique_rows_counts_per_feature():
    # feature 0: {7, 9}; feature 1: {7}; feature 2: {1, 2, 3}
    ids = np.array([[7, 7, 1], [9, 7, 2], [7, 7, 3]], np.uint64)
    assert counts.unique_rows({"ids": ids}) == 6
    assert counts.mean_unique_rows([{"ids": ids}, {"ids": ids[:1]}]) == 4.5


@pytest.mark.parametrize("fn,want", [
    (counts.gather_bytes, 6 * 20 * 3),      # pull + re-read of row and slot
    (counts.scatter_bytes, 6 * 20 * 2),     # row and slot written
    (counts.step_hbm_bytes, 6 * 20 * 5),
])
def test_step_bytes(fn, want):
    assert fn(TINY, 6) == want


def test_dense_flops_per_example():
    # width 3*4+2 = 14; MLP 14->8->5->1: 2*(112+40+5) = 314; FM 4*3*4 = 48;
    # forward + backward at twice the forward = 3x
    assert counts.dense_flops_per_example(TINY) == 3 * (314 + 48)


def test_seeded_rows_same_bits_on_numpy_and_jax():
    import jax.numpy as jnp
    f, lo, hi = np.arange(6) % 3, np.arange(6) * 977, np.arange(6) * 3
    a = seeded.table_rows(3000000019, 1, f, lo, hi, 4, 0.05)
    b = seeded.table_rows(3000000019, 1, jnp.asarray(f), jnp.asarray(lo),
                          jnp.asarray(hi), 4, 0.05, jnp)
    assert a.dtype == np.float32 and np.array_equal(a, np.asarray(b))
    assert np.abs(a).max() < 0.05 and len(np.unique(a)) == a.size
    other = seeded.table_rows(3000000020, 1, f, lo, hi, 4, 0.05)
    assert not np.array_equal(a, other)

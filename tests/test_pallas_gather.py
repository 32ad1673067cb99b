"""Pallas sparse-gather kernel: parity with the XLA pull contract.

Runs in interpret mode on the CPU mesh. On a TPU the kernel compiles to a
Mosaic pipeline (``tests/test_tpu_lowering.py`` compiles it for v5e,
``chip_smoke.py`` runs it there); XLA's gather remains the default pull
path, the kernel is the native-op scaffold."""

import numpy as np

import jax
import jax.numpy as jnp

import pytest

from openembedding_tpu.ops.pallas_gather import (ROWS_PER_STEP, gather_rows,
                                                 pad_table)


def test_gather_parity_and_invalid_ids(devices8):
    rng = np.random.RandomState(0)
    table = pad_table(jnp.asarray(rng.randn(100, 9).astype(np.float32)))
    idx = jnp.asarray([0, 5, 99, -1, 100, 5, 42], jnp.int32)
    got = np.asarray(gather_rows(table, idx, interpret=True))[:, :9]
    want = np.zeros((7, 9), np.float32)
    for i, v in enumerate([0, 5, 99, -1, -1, 5, 42]):
        if v >= 0:
            want[i] = np.asarray(table)[v, :9]
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("dtype,dim", [(jnp.float32, 9), (jnp.float32, 256),
                                       (jnp.bfloat16, 128)])
def test_gather_refuses_what_mosaic_refuses(devices8, dtype, dim):
    """The guard raises before lowering for every row shape other than
    float32 x 128 (the one Mosaic compiles the one-row DMA for)."""
    table = jnp.zeros((16, dim), dtype)
    with pytest.raises(ValueError, match="float32 rows of exactly 128"):
        gather_rows(table, jnp.zeros((4,), jnp.int32), interpret=True)


def test_probe_gather_refuses_what_mosaic_refuses(devices8):
    from openembedding_tpu.ops.pallas_hash import probe_gather
    q = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="float32 rows of exactly 128"):
        probe_gather(jnp.zeros((256,), jnp.int32),
                     jnp.zeros((256, 256), jnp.float32), q, q,
                     chain=2, bucket=128, empty=0, interpret=True)
    with pytest.raises(ValueError, match="128-slot buckets"):
        probe_gather(jnp.zeros((64,), jnp.int32),
                     jnp.zeros((64, 128), jnp.float32), q, q,
                     chain=1, bucket=64, empty=0, interpret=True)
    with pytest.raises(ValueError, match="int32 keys"):
        probe_gather(jnp.zeros((256,), jnp.int16),
                     jnp.zeros((256, 128), jnp.float32), q, q,
                     chain=2, bucket=128, empty=0, interpret=True)


def test_gather_lane_aligned_and_step_multiple(devices8):
    """dim already lane-aligned + batch an exact multiple of the DMA depth."""
    rng = np.random.RandomState(1)
    table = jnp.asarray(rng.randn(64, 128).astype(np.float32))
    idx = jnp.asarray(rng.randint(0, 64, 4 * ROWS_PER_STEP), jnp.int32)
    got = np.asarray(gather_rows(table, idx, interpret=True))
    np.testing.assert_allclose(got, np.asarray(table)[np.asarray(idx)],
                               rtol=1e-6)

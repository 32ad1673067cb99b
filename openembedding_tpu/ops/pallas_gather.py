"""Pallas TPU kernel: sparse row gather (embedding pull) with manual DMA.

The hot op of this framework is "fetch B*F scattered rows from a [V, D]
table in HBM" — the job the reference hand-writes in its C++ pull pipeline
(server row copies + response scatter, EmbeddingPullOperator.cpp:149-252).
XLA's native gather is strong on TPU (and remains the default pull path);
this kernel is the native-kernel form of the same op and the scaffold for
fusions XLA cannot express (gather + probe, gather + on-the-fly dedup):

* the index vector rides **scalar prefetch** (PrefetchScalarGridSpec) so
  row addresses are known before the body runs;
* the table stays in **HBM** (``pl.ANY``); each grid step issues R
  parallel row DMAs HBM->VMEM scratch (R in flight hides latency), waits,
  masks invalid ids to zero rows, and writes the output block;
* invalid ids (< 0 or >= V) are clamped for the DMA and zeroed in the
  body — the framework-wide invalid-index contract.

``interpret=True`` runs on CPU (tests); on TPU it compiles to a Mosaic
pipeline. Admitted tables are float32 with rows of exactly one 128-lane
tile: there a row is contiguous in HBM and the one-row DMA slice lowers.
Wider rows or 2-byte dtypes tile HBM as (8, 128) / (16, 128) blocks and
Mosaic refuses the slice ("must be aligned to tiling (8), but is 1"), so
:func:`check_row_tile` raises before lowering. Padding inside the call
would materialize a full padded table copy per gather: use
:func:`pad_table` ONCE at table-creation time if the model dim is ragged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS_PER_STEP = 8  # DMAs in flight per grid step (one output sublane tile)
LANES = 128        # the one row width the row DMA compiles for


def _gather_kernel(idx_ref, table_ref, out_ref, scratch, sems):
    i = pl.program_id(0)
    vocab = idx_ref[-1]
    for r in range(ROWS_PER_STEP):
        row = idx_ref[i * ROWS_PER_STEP + r]
        safe = jnp.clip(row, 0, vocab - 1)
        pltpu.make_async_copy(
            table_ref.at[pl.dslice(safe, 1), :],
            scratch.at[pl.dslice(r, 1), :],
            sems.at[r],
        ).start()
    for r in range(ROWS_PER_STEP):
        row = idx_ref[i * ROWS_PER_STEP + r]
        safe = jnp.clip(row, 0, vocab - 1)
        pltpu.make_async_copy(
            table_ref.at[pl.dslice(safe, 1), :],
            scratch.at[pl.dslice(r, 1), :],
            sems.at[r],
        ).wait()
        valid = (row >= 0) & (row < vocab)
        out_ref[pl.dslice(r, 1), :] = jnp.where(
            valid, scratch[pl.dslice(r, 1), :], 0.0).astype(out_ref.dtype)


def check_row_tile(what: str, dtype, dim: int) -> None:
    """Raise ``ValueError`` unless ``[rows, dim]`` of ``dtype`` is a shape
    the one-row HBM DMA of these kernels compiles for on TPU v5 lite
    (shared by :mod:`.pallas_hash`; AOT-checked per admitted and refused
    shape in ``tests/test_tpu_lowering.py``)."""
    if jnp.dtype(dtype) != jnp.float32 or dim != LANES:
        raise ValueError(
            f"{what}: rows are {jnp.dtype(dtype).name} x {dim}; the kernel "
            f"compiles only for float32 rows of exactly {LANES} lanes "
            "(Mosaic refuses the one-row HBM slice under any other "
            f"tiling). Pad a narrower table once with "
            "pallas_gather.pad_table; wider or 2-byte rows take the XLA "
            "gather")


def pad_table(table: jnp.ndarray) -> jnp.ndarray:
    """Pad the row dim to the 128-lane boundary (do this ONCE at table
    creation, not per lookup — the copy is table-sized)."""
    dim = table.shape[1]
    dpad = -(-dim // LANES) * LANES
    if dpad == dim:
        return table
    return jnp.pad(table, ((0, 0), (0, dpad - dim)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(table: jnp.ndarray, indices: jnp.ndarray,
                *, interpret: bool = False) -> jnp.ndarray:
    """rows[i] = table[indices[i]] with zero rows for invalid ids.

    Drop-in for the gather inside ``table.pull`` — same contract, Pallas
    manual-DMA pipeline instead of XLA gather. ``indices`` is [n] int;
    returns [n, dim] in the table dtype. The table must be float32 with
    128-lane rows (:func:`check_row_tile`, :func:`pad_table`).
    """
    n = indices.shape[0]
    vocab, dim = table.shape
    check_row_tile("gather_rows table", table.dtype, dim)
    dpad = dim
    npad = -(-n // ROWS_PER_STEP) * ROWS_PER_STEP
    # bounds-check in the ORIGINAL dtype: an int64 id >= 2^32 must become an
    # invalid (-1) row, not wrap onto a real one through the int32 cast
    valid = (indices >= 0) & (indices < vocab)
    idx = jnp.where(valid, indices, -1).astype(jnp.int32)
    if npad != n:
        idx = jnp.pad(idx, (0, npad - n), constant_values=-1)
    # the kernel needs the vocab bound; smuggle it as the last prefetch slot
    idx_plus = jnp.concatenate([idx, jnp.asarray([vocab], jnp.int32)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(npad // ROWS_PER_STEP,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # table in HBM
        out_specs=pl.BlockSpec((ROWS_PER_STEP, dpad),
                               lambda i, idx_ref: (i, 0)),
        scratch_shapes=[
            pltpu.VMEM((ROWS_PER_STEP, dpad), table.dtype),
            pltpu.SemaphoreType.DMA((ROWS_PER_STEP,)),
        ],
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((npad, dpad), table.dtype),
        interpret=interpret,
    )(idx_plus, table)
    return out[:n, :dim]

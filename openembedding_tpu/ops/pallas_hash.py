"""Pallas TPU kernel: fused hash-probe + row gather (hash-table pull).

The reference's server-side hash pull is a single C++ loop: probe the
EasyHashMap, copy the matched row into the response
(/root/reference/openembedding/server/EmbeddingPullOperator.cpp:149-252).
The XLA composition splits it into two HBM passes — gather the [n, W]
probe-chain keys, argmax the match, then gather the [n, dim] rows. This
kernel is the reference's loop as one Mosaic pipeline:

* probe starts ride **scalar prefetch** so chain addresses are known before
  the body runs. ``hash_table`` lays the slot space out in 128-slot buckets
  and bounds every chain to consecutive buckets, so a query's candidate
  keys are ONE aligned ``[chain, 128]`` DMA from the ``[num_buckets, 128]``
  key array — no wraparound, no unaligned 1D slices (Mosaic tiles 1D HBM
  refs in 1024-element units and refuses unaligned windows);
* each grid step keeps R queries in flight: key-chain DMAs HBM->VMEM,
  vectorized compare + sum-reduction to the match offset, then the matched
  row's DMA — the probe result never round-trips through HBM;
* misses and EMPTY-sentinel queries yield zero rows and ``hit=0`` — the
  caller overlays deterministic init rows for training pulls (serving
  pulls use zeros directly, the read-only contract).

``interpret=True`` runs the same kernel on CPU (tests); on TPU it compiles
to a Mosaic pipeline. int64-key tables fall back to the XLA path (scalar
prefetch is int32; wide keys route through the hi/lo pair plane).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_gather import LANES, check_row_tile

ROWS_PER_STEP = 16  # queries in flight per grid step


def _probe_gather_kernel(bkt_ref, qkeys_ref, tkeys_ref, weights_ref,
                         rows_ref, hit_ref, kscratch, rscratch, ksem, rsem,
                         *, chain: int, bucket: int, empty: int,
                         nsteps: int):
    """Double-buffered probe: key-chain DMAs for grid step i+1 are issued
    while step i computes, so the per-query DMA latency (the measured
    bottleneck of the single-buffered version: 2 serial DMAs per query
    issued from the scalar core) hides behind the compare/row phase.
    Buffer parity is resolved with static indices under even/odd
    ``pl.when`` branches (dynamic scratch/semaphore indices don't lower).
    """
    i = pl.program_id(0)
    R = ROWS_PER_STEP

    def key_copy(step, r, buf):
        b = bkt_ref[step * R + r]
        return pltpu.make_async_copy(
            tkeys_ref.at[pl.dslice(b, chain), :],
            kscratch.at[pl.dslice((buf * R + r) * chain, chain), :],
            ksem.at[buf * R + r])

    parity = jax.lax.rem(i, 2)

    @pl.when(i == 0)
    def _():  # prime the pipeline: this step's own chains
        for r in range(R):
            key_copy(i, r, 0).start()

    @pl.when(i + 1 < nsteps)
    def _():  # prefetch the NEXT step's chains into the other buffer
        for buf in (0, 1):  # static-index twin branches
            @pl.when(parity == buf)
            def _(buf=buf):
                for r in range(R):
                    key_copy(i + 1, r, 1 - buf).start()

    def body(buf):
        hits = []
        for r in range(R):
            key_copy(i, r, buf).wait()
            q = qkeys_ref[i * R + r]
            window = kscratch[
                pl.dslice((buf * R + r) * chain, chain), :]
            match = window == q
            # unique keys: at most one slot matches -> sum IS the offset
            iota = jax.lax.broadcasted_iota(
                jnp.int32, (chain, bucket), 1) + \
                jax.lax.broadcasted_iota(
                    jnp.int32, (chain, bucket), 0) * bucket
            off = jnp.sum(jnp.where(match, iota, 0))
            nhit = jnp.sum(match.astype(jnp.int32))
            hit = (nhit > 0) & (q != empty)
            hits.append(hit)
            b = bkt_ref[i * R + r]
            row = jnp.where(hit, b * bucket + off, 0)
            pltpu.make_async_copy(
                weights_ref.at[pl.dslice(row, 1), :],
                rscratch.at[pl.dslice(r, 1), :], rsem.at[r]).start()

        for r in range(R):
            # wait on the row DMA (same byte count; only the sem matters)
            pltpu.make_async_copy(
                weights_ref.at[pl.dslice(0, 1), :],
                rscratch.at[pl.dslice(r, 1), :], rsem.at[r]).wait()
            rows_ref[pl.dslice(r, 1), :] = jnp.where(
                hits[r], rscratch[pl.dslice(r, 1), :],
                jnp.zeros_like(rscratch[pl.dslice(r, 1), :]))

        # scalar stores to VMEM are disallowed: write hits vectorized
        hit_ref[:, :] = jnp.stack(
            [h.astype(jnp.int32) for h in hits]).reshape(R, 1)

    for buf in (0, 1):
        @pl.when(parity == buf)
        def _(buf=buf):
            body(buf)


@functools.partial(jax.jit,
                   static_argnames=("chain", "bucket", "empty", "interpret"))
def probe_gather(table_keys: jnp.ndarray, weights: jnp.ndarray,
                 starts: jnp.ndarray, query: jnp.ndarray,
                 *, chain: int, bucket: int, empty: int,
                 interpret: bool = False):
    """Fused lookup: ``rows[i] = weights[slot(query[i])]``, zeros on miss.

    ``starts`` are the per-query aligned probe starts
    (``hash_table.probe_starts``); the ``chain * bucket`` slots from each
    start are compared against the query key and the matched row is DMA'd
    directly. Returns ``(rows [n, dim], hit [n] bool)``. The weights must
    be float32 with 128-lane rows (``pallas_gather.check_row_tile``; pad
    the TABLE once at creation if needed, ``pallas_gather.pad_table``).
    """
    n = query.shape[0]
    capacity = table_keys.shape[0]
    dim = weights.shape[1]
    if query.dtype != jnp.int32 or table_keys.dtype != jnp.int32:
        # int64 keys would alias mod 2^32 through the int32 scalar-prefetch
        # cast — wide keys must use the XLA path (module contract)
        raise ValueError(
            f"probe_gather requires int32 keys (got query "
            f"{query.dtype}, table {table_keys.dtype}); int64-key tables "
            "use the XLA probe path")
    check_row_tile("probe_gather weights", weights.dtype, dim)
    # `bucket` is a static argument (a Python int under the trace)
    if bucket != LANES or capacity % bucket:  # graftlint: disable=JG003
        # sub-bucket tables (capacity < 128) collapse to one short bucket
        # whose key row is not a lane tile
        raise ValueError(
            f"probe_gather needs {LANES}-slot buckets and a capacity that "
            f"is a multiple of them (got bucket {bucket}, capacity "
            f"{capacity})")
    npad = -(-n // ROWS_PER_STEP) * ROWS_PER_STEP
    bkt = (starts // bucket).astype(jnp.int32)
    qk = query.astype(jnp.int32)
    if npad != n:
        bkt = jnp.pad(bkt, (0, npad - n))
        qk = jnp.pad(qk, (0, npad - n), constant_values=empty)
    keys2d = table_keys.reshape(capacity // bucket, bucket)

    nsteps = npad // ROWS_PER_STEP
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nsteps,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),   # keys in HBM
                  pl.BlockSpec(memory_space=pl.ANY)],  # weights in HBM
        out_specs=[pl.BlockSpec((ROWS_PER_STEP, dim),
                                lambda i, s, q: (i, 0)),
                   pl.BlockSpec((ROWS_PER_STEP, 1),
                                lambda i, s, q: (i, 0))],
        scratch_shapes=[
            # x2: double-buffered key staging (this step + the prefetched
            # next step); scratch persists across sequential grid steps
            pltpu.VMEM((2 * ROWS_PER_STEP * chain, bucket),
                       table_keys.dtype),
            pltpu.VMEM((ROWS_PER_STEP, dim), weights.dtype),
            pltpu.SemaphoreType.DMA((2 * ROWS_PER_STEP,)),
            pltpu.SemaphoreType.DMA((ROWS_PER_STEP,)),
        ],
    )
    rows, hit = pl.pallas_call(
        functools.partial(_probe_gather_kernel, chain=chain, bucket=bucket,
                          empty=empty, nsteps=nsteps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((npad, dim), weights.dtype),
                   jax.ShapeDtypeStruct((npad, 1), jnp.int32)],
        interpret=interpret,
    )(bkt, qk, keys2d, weights)
    return rows[:n], hit[:n, 0] > 0

"""Single-shard embedding table: functional pull / push+update.

TPU-native redesign of the reference's variable layer
(/root/reference/openembedding/variable/EmbeddingTable.h:121-197 array table,
EmbeddingOptimizerVariable.h:242-297 pull/push/update composition):

* The table is a dense ``[capacity, dim]`` array in HBM plus named optimizer
  slot arrays co-indexed with it — the reference's "weights and optimizer
  state contiguous per row" layout, split into parallel arrays so XLA keeps
  each slot contiguous and fuses the update elementwise.
* ``pull``: one gather. The reference's deferred materialization (_new_weights
  side table for unseen keys) is unnecessary because rows are initialized
  eagerly at creation with a PRNG (statistically identical, compiler-friendly).
* ``apply_gradients`` replaces the reference's push + store pipeline
  (MpscGradientReducer reduce → per-row optimizer update under shard lock):
  capacity-padded dedup, scatter-add combine, gather touched rows, vectorized
  optimizer ``update_rows``, scatter back. Exactly the touched-rows-only
  sparse semantics, in one fused XLA program instead of two RPC round trips.

The hash-table variant for unbounded (2^63) key spaces lives in
``hash_table.py``; both present the same pull/apply surface.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from flax import struct

from .analysis import scope
from .meta import EmbeddingVariableMeta
from .ops import dedup
from .optim.initializers import Initializer, make_initializer
from .optim.optimizers import SparseOptimizer, make_optimizer


# Shared default: small-uniform like the reference's default variable config.
DEFAULT_INITIALIZER = {"category": "uniform", "minval": -1e-3, "maxval": 1e-3}


def resolve_dtype(meta: EmbeddingVariableMeta):
    """Table dtype with the x64 guard (float64 needs jax_enable_x64)."""
    dtype = jnp.dtype(meta.datatype)
    if dtype == jnp.float64 and not jax.config.jax_enable_x64:
        raise ValueError(
            "datatype='float64' requires jax_enable_x64; enable it with "
            "jax.config.update('jax_enable_x64', True) or use float32/bfloat16")
    return dtype


@struct.dataclass
class TableState:
    """Pytree holding one shard's weights + optimizer slots."""

    weights: jnp.ndarray                 # [capacity, dim]
    slots: Dict[str, jnp.ndarray]        # each [capacity, ...]

    @property
    def capacity(self) -> int:
        return self.weights.shape[0]

    @property
    def dim(self) -> int:
        return self.weights.shape[1]


def create_table(meta: EmbeddingVariableMeta,
                 optimizer: Any,
                 initializer: Any = None,
                 *,
                 rng: Optional[jax.Array] = None,
                 capacity: Optional[int] = None) -> TableState:
    """Materialize a table shard (weights initialized, slots at their init value).

    ``capacity`` defaults to ``meta.vocabulary_size`` (the whole table — use
    the sharded wrappers in ``parallel/`` to build per-shard slices).
    """
    optimizer = make_optimizer(optimizer)
    initializer = make_initializer(initializer or DEFAULT_INITIALIZER)
    if capacity is None:
        capacity = meta.vocabulary_size
    if rng is None:
        rng = jax.random.PRNGKey(0)
    dtype = resolve_dtype(meta)
    weights = initializer.init(rng, (capacity, meta.embedding_dim), dtype)
    slots = optimizer.init_slots(capacity, meta.embedding_dim, dtype)
    return TableState(weights=weights, slots=slots)


def pull(state: TableState, indices: jnp.ndarray) -> jnp.ndarray:
    """Embedding lookup: rows for (possibly duplicated) indices.

    Invalid indices (negative or >= capacity) return zero rows — the same
    contract as the sharded path and as apply_gradients, which drops them.
    Output shape = indices.shape + [dim].
    """
    @scope.stage("resolve")
    def read(weights, flat):
        valid = (flat >= 0) & (flat < state.capacity)
        rows = jnp.take(weights, jnp.where(valid, flat, 0), axis=0,
                        mode="clip")
        return jnp.where(valid[:, None], rows, jnp.zeros_like(rows))

    return read(state.weights, indices.ravel()).reshape(
        indices.shape + (state.dim,))


def optimizer_block_update(optimizer: SparseOptimizer,
                           weights: jnp.ndarray,
                           slots: Dict[str, jnp.ndarray],
                           summed: jnp.ndarray,
                           counts: jnp.ndarray):
    """One vectorized optimizer step over a gathered [U, D] row block,
    with the framework-wide storage-dtype contract: math runs at >=
    float32 even for bfloat16 tables, results are cast back to each
    array's storage dtype. Shared by the array/hash apply paths and the
    hot-row replica update (``parallel/hot_cache.py``)."""
    @scope.stage("apply_update")
    def update(weights, slots, summed, counts):
        compute = jnp.promote_types(weights.dtype, jnp.float32)
        new_w, new_s = optimizer.update_rows(
            weights.astype(compute),
            {k: v.astype(jnp.promote_types(v.dtype, jnp.float32))
             for k, v in slots.items()},
            summed.astype(compute), counts)
        new_w = new_w.astype(weights.dtype)
        new_s = {k: new_s[k].astype(slots[k].dtype) for k in new_s}
        return new_w, new_s

    return update(weights, slots, summed, counts)


def apply_gradients(state: TableState,
                    optimizer: SparseOptimizer,
                    indices: jnp.ndarray,
                    grads: jnp.ndarray,
                    *,
                    dedup_capacity: Optional[int] = None,
                    in_counts: Optional[jnp.ndarray] = None) -> TableState:
    """Push + update in one step: combine duplicate grads, update touched rows.

    ``indices`` is [n] (or any shape), ``grads`` matches with a trailing
    [dim]. Rows not referenced are untouched (no state decay), duplicates are
    summed with counts — the reference's documented sparse-update contract.
    ``in_counts`` ([n]) marks grads that are already pre-reduced sums of that
    many originals (owner side of the all-to-all exchange).
    """
    dim = state.dim
    flat_idx = indices.ravel()
    flat_grads = grads.reshape(-1, dim)
    n = flat_idx.shape[0]
    capacity = dedup_capacity or n

    uniq, inverse, valid = dedup.unique_indices(flat_idx, capacity)
    # negative indices are invalid keys: pull clamps them to row 0, the
    # update must NOT let them wrap around onto a real row.
    valid = valid & (uniq >= 0)
    summed, counts = dedup.combine_gradients(flat_grads, inverse, capacity,
                                             in_counts)

    # Gather touched rows + slots; padding slots gather row 0 then are dropped
    # on the scatter, so their (garbage) update never lands.
    safe_uniq = jnp.where(valid, uniq, 0)
    w, s = gather_rows(state.weights, state.slots, safe_uniq)

    new_w, new_s = optimizer_block_update(optimizer, w, s, summed, counts)

    oob = jnp.asarray(state.capacity, dtype=safe_uniq.dtype)
    scatter_idx = jnp.where(valid, safe_uniq, oob)  # padding -> dropped
    weights, slots = scatter_rows(state.weights, state.slots, scatter_idx,
                                  new_w, new_s)
    return TableState(weights=weights, slots=slots)


def gather_rows(weights: jnp.ndarray, slots: Dict[str, jnp.ndarray],
                at: jnp.ndarray):
    """Rows of the weights and of every slot array at ``at``: the read
    half of a sparse update (array and hash apply paths)."""
    @scope.stage("apply_gather")
    def gather(weights, slots, at):
        return (jnp.take(weights, at, axis=0),
                {k: jnp.take(v, at, axis=0) for k, v in slots.items()})

    return gather(weights, slots, at)


def scatter_rows(weights: jnp.ndarray, slots: Dict[str, jnp.ndarray],
                 at: jnp.ndarray, new_w: jnp.ndarray,
                 new_s: Dict[str, jnp.ndarray]):
    """Write updated rows back at ``at``; out-of-range entries (padding)
    are dropped. The write half of a sparse update."""
    @scope.stage("apply_scatter")
    def scatter(weights, slots, at, new_w, new_s):
        return (weights.at[at].set(new_w, mode="drop"),
                {k: slots[k].at[at].set(new_s[k], mode="drop")
                 for k in slots})

    return scatter(weights, slots, at, new_w, new_s)

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
  capacity-padded dedup, scatter-add combine, then gather touched rows,
  vectorized optimizer ``update_rows``, scatter back (``apply_rows``, shared
  with the hash table). Exactly the touched-rows-only sparse semantics, in
  one fused XLA program instead of two RPC round trips. The gather, update
  and scatter walk only the occupied prefix of the padded unique buffer, in
  fixed chunks: the apply's cost follows the distinct rows of the batch,
  not ``dedup_capacity``.

The hash-table variant for unbounded (2^63) key spaces lives in
``hash_table.py``; both present the same pull/apply surface.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from flax import struct

from .analysis import scope
from .meta import EmbeddingVariableMeta
from .ops import dedup
from .optim.initializers import Initializer, make_initializer
from .optim.optimizers import SparseOptimizer, make_optimizer
from .parallel.alltoall import record_stat


# Shared default: small-uniform like the reference's default variable config.
DEFAULT_INITIALIZER = {"category": "uniform", "minval": -1e-3, "maxval": 1e-3}

# Slots of the unique buffer one trip of the sparse apply gathers, updates
# and scatters (apply_rows). Fixed from runs on a TPU v5e: PERF.md section 6.
APPLY_CHUNK = 4096

# Keys of the same buffer one trip of the hash push's find probes
# (hash_table.find_or_insert); fixed the same way.
FIND_CHUNK = 4096

# Missed keys one trip of the hash push's compact insert loop places
# (hash_table.find_or_insert); fixed the same way.
INSERT_CHUNK = 1024


def occupied_prefix(mask: jnp.ndarray) -> jnp.ndarray:
    """One past the last set position of ``mask`` [n], 0 where none is set:
    the part of a unique buffer a chunked walk has to visit. Right for any
    mask; a prefix-shaped one (both dedups leave the live slots in front)
    makes it the number of live slots."""
    return jnp.max(jnp.where(
        mask, jnp.arange(1, mask.shape[0] + 1, dtype=jnp.int32), 0))


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


def merge_gradients(indices: jnp.ndarray, grads: jnp.ndarray, *,
                    dedup_capacity: Optional[int] = None,
                    in_counts: Optional[jnp.ndarray] = None,
                    plan: Optional[dedup.Plan] = None):
    """The first half of :func:`apply_gradients`, which needs no table:
    deduplicate ``indices`` and combine their gradients into a buffer of
    ``dedup_capacity`` (default ``n``) slots. Returns :func:`apply_rows`'s
    ``(rows, live, summed, counts)``. ``plan`` is the dedup of ``indices``
    where the step has made it already, in front of its pull: its slots
    are the buffer and nothing is deduplicated again (nor counted, where
    the plan brings its ``counts``)."""
    flat_grads = grads.reshape(-1, grads.shape[-1])
    if plan is None:
        flat_idx = indices.ravel()
        capacity = dedup_capacity or flat_idx.shape[0]
        uniq, inverse, valid = dedup.unique_indices(flat_idx, capacity)
    else:
        uniq, inverse, valid = plan.uniq, plan.inverse, plan.valid
        capacity = uniq.shape[0]
    # negative indices are invalid keys: pull clamps them to row 0, the
    # update must NOT let them wrap around onto a real row.
    valid = valid & (uniq >= 0)
    summed, counts = dedup.combine_gradients(
        flat_grads, inverse, capacity, in_counts,
        counts=None if plan is None else plan.counts)
    return uniq, valid, summed, counts


def apply_gradients(state: TableState,
                    optimizer: SparseOptimizer,
                    indices: jnp.ndarray,
                    grads: jnp.ndarray,
                    *,
                    dedup_capacity: Optional[int] = None,
                    in_counts: Optional[jnp.ndarray] = None,
                    record_stats: bool = False,
                    plan: Optional[dedup.Plan] = None,
                    resolved: Optional[dedup.Resolution] = None
                    ) -> TableState:
    """Push + update in one step: combine duplicate grads, update touched rows.

    ``indices`` is [n] (or any shape), ``grads`` matches with a trailing
    [dim]. Rows not referenced are untouched (no state decay), duplicates are
    summed with counts — the reference's documented sparse-update contract.
    ``in_counts`` ([n]) marks grads that are already pre-reduced sums of that
    many originals (owner side of the all-to-all exchange).

    The dedup and the combine (:func:`merge_gradients`) run over
    ``dedup_capacity`` slots; the gather, the optimizer and the scatter run
    over the distinct rows of the batch (:func:`apply_rows`), so their cost
    follows those and not ``dedup_capacity``. ``record_stats`` is
    :func:`apply_rows`'s, ``plan`` :func:`merge_gradients`'s. ``resolved``
    is what the step's pull read for the plan's slots
    (:func:`read_distinct` of the same rows, the table unwritten since):
    its rows are :func:`apply_rows`'s ``pulled``.
    """
    merged = merge_gradients(indices, grads, dedup_capacity=dedup_capacity,
                             in_counts=in_counts, plan=plan)
    weights, slots = apply_rows(
        state.weights, state.slots, optimizer, *merged,
        pulled=None if resolved is None else resolved.rows,
        record_stats=record_stats)
    return TableState(weights=weights, slots=slots)


def apply_rows(weights: jnp.ndarray, slots: Dict[str, jnp.ndarray],
               optimizer: SparseOptimizer, rows: jnp.ndarray,
               live: jnp.ndarray, summed: jnp.ndarray, counts: jnp.ndarray,
               fresh: Optional[jnp.ndarray] = None,
               inserted: Optional[jnp.ndarray] = None,
               *, pulled: Optional[jnp.ndarray] = None,
               record_stats: bool = False):
    """The sparse apply over a deduplicated buffer: gather the rows, run the
    optimizer, scatter them back. Shared by the array and the hash
    ``apply_gradients``.

    ``rows`` [capacity] is each buffer slot's table row, ``live`` the slots
    that hold one (a dead slot's row may be anything: it is read as row 0
    and its write is dropped), ``summed`` [capacity, dim] and ``counts``
    the combined gradients. ``fresh`` [capacity, dim] replaces the gathered
    weights where ``inserted`` is set (the hash path's new keys).
    ``pulled`` [capacity, dim] is every live slot's weight row where the
    caller holds it already (a train step's pull read it for the same
    buffer, ``dedup.Resolution.rows``: the stored row, or the init row of
    a key its push inserts, so no ``fresh`` goes with it): a trip slices it
    as it slices ``summed`` and gathers the optimizer's slot arrays alone.

    Both dedups leave the live slots in a prefix of the buffer, and a
    batch's distinct rows fill a fraction of it (a third, for Criteo-shaped
    ids). So the buffer is walked in chunks of :data:`APPLY_CHUNK` slots up
    to the last live one, in a loop that carries the table: the cost follows
    the rows a step touches, not the buffer's capacity. A buffer of one
    chunk or less takes the body once, with no loop. Every live row is
    distinct, so the rows written and their values are the one-pass
    apply's. Arrays of one-element rows are the exception in how, not in
    what: the trips stage their new rows and one scatter after the loop
    writes them.

    ``record_stats`` (the trace-time gate of ``alltoall.record_stat``)
    counts ``apply_slots_live`` and ``apply_slots_walked`` a call: the
    second over the buffer's capacity is the share of it the loop walked;
    and ``push_rows_carried``, the live slots whose weight row came as
    ``pulled``.
    """
    capacity = rows.shape[0]
    chunk = APPLY_CHUNK
    oob = jnp.asarray(weights.shape[0], rows.dtype)
    arrays, tree = jax.tree.flatten((weights, slots))

    def updated(arrays, rows, live, summed, counts, fresh, inserted, pulled):
        """(where a run of slots is written, its new rows array by array)."""
        # a dead slot gathers row 0 and is dropped on the scatter, so its
        # (garbage) update never lands
        at = jnp.where(live, rows, 0)
        w, s = jax.tree.unflatten(tree, arrays)
        if pulled is None:
            w, s = gather_rows(w, s, at)
            if fresh is not None:
                w = jnp.where(inserted[:, None], fresh, w)
        else:
            w, (_, s) = pulled, gather_rows(None, s, at)
        new = optimizer_block_update(optimizer, w, s, summed, counts)
        return jnp.where(live, at, oob), jax.tree.leaves(new)

    # An array of one-element rows (a linear column, Adam's powers) is
    # written once, after the loop: the v5e compiler scatters into it by a
    # pass of ~1.5 ms whatever the count above ~50,000 slots, and below
    # that row by row, for as much a trip of 4,096 (PERF.md section 6).
    late = [x[0].size == 1 for x in arrays]

    def pick(xs, when):
        return [x for x, last in zip(xs, late) if last == when]

    def put(xs, when, ys):
        ys = iter(ys)
        return [next(ys) if last == when else x for x, last in zip(xs, late)]

    # the loop's own instructions (slices, the trip count) read as
    # apply_update; the trips' gathers and scatters keep their stages
    @scope.stage("apply_update")
    def walk(arrays, *per_slot):
        trips = (occupied_prefix(per_slot[1]) + (chunk - 1)) // chunk
        # whole chunks only: a last slice clamped backwards would gather a
        # row the trip before it had updated, and apply its gradient twice
        short = -capacity % chunk
        per_slot = jax.tree.map(
            lambda x: jnp.pad(x, [(0, short)] + [(0, 0)] * (x.ndim - 1)),
            per_slot)

        def trip(i, carry):
            arrays, staged = carry
            part = jax.tree.map(
                lambda x: lax.dynamic_slice_in_dim(x, i * chunk, chunk),
                per_slot)
            at, new = updated(arrays, *part)
            arrays = put(arrays, False, scatter_rows(
                pick(arrays, False), at, pick(new, False)))
            staged = [lax.dynamic_update_slice_in_dim(x, n, i * chunk, 0)
                      for x, n in zip(staged, pick(new, True))]
            return arrays, staged

        arrays, staged = lax.fori_loop(0, trips, trip, (arrays, [
            jnp.zeros((capacity + short,) + x.shape[1:], x.dtype)
            for x in pick(arrays, True)]))
        if staged:
            rows, live = per_slot[:2]
            arrays = put(arrays, True, scatter_rows(
                pick(arrays, True), jnp.where(live, rows, oob), staged))
        return arrays, trips * chunk

    per_slot = (rows, live, summed, counts, fresh, inserted, pulled)
    if capacity <= chunk:
        arrays = scatter_rows(arrays, *updated(arrays, *per_slot))
        walked = jnp.int32(capacity)
    else:
        arrays, walked = walk(arrays, *per_slot)
    record_stat("apply_slots_live", jnp.sum(live, dtype=jnp.int32),
                record_stats)
    record_stat("apply_slots_walked", walked, record_stats)
    if pulled is not None:
        record_stat("push_rows_carried", jnp.sum(live, dtype=jnp.int32),
                    record_stats)
    return jax.tree.unflatten(tree, arrays)


def gather_rows(weights: Optional[jnp.ndarray],
                slots: Dict[str, jnp.ndarray], at: jnp.ndarray):
    """Rows of the weights and of every slot array at ``at``: the read
    half of a sparse update (array and hash apply paths). ``weights`` None:
    the caller holds those rows, and None is what it gets for them."""
    @scope.stage("apply_gather")
    def gather(weights, slots, at):
        return (None if weights is None else jnp.take(weights, at, axis=0),
                {k: jnp.take(v, at, axis=0) for k, v in slots.items()})

    return gather(weights, slots, at)


def pulled_rows(weights: jnp.ndarray, rows: jnp.ndarray, live: jnp.ndarray,
                fresh: Optional[jnp.ndarray] = None,
                inserted: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The weight rows :func:`apply_rows` reads for a buffer it is handed
    no ``pulled`` for, in one pass: its ``pulled``, for a caller that has
    to hand over one structure whether a pull resolved the buffer or not
    (the routed push's two branches, ``parallel/sharded.py``)."""
    w, _ = gather_rows(weights, {}, jnp.where(live, rows, 0))
    return w if fresh is None else jnp.where(inserted[:, None], fresh, w)


def read_rows(arrays, at: jnp.ndarray, count: jnp.ndarray):
    """Copies of the rows ``at[:count]`` of every array of ``arrays``, in
    buffers of ``at``'s length whose rest is zero. Read as the sparse apply
    reads rows, :data:`APPLY_CHUNK` a trip, so the cost follows ``count``
    and not ``at``'s length; an index past an array's end reads a zero
    row."""
    capacity = at.shape[0]
    chunk = min(APPLY_CHUNK, capacity)
    short = -capacity % chunk       # whole chunks only, as apply_rows pads
    if short:
        at = jnp.pad(at, (0, short))

    def trip(i, staged):
        part = lax.dynamic_slice_in_dim(at, i * chunk, chunk)
        return [lax.dynamic_update_slice_in_dim(
            s, jnp.take(x, part, axis=0, mode="fill", fill_value=0),
            i * chunk, 0) for s, x in zip(staged, arrays)]

    staged = lax.fori_loop(
        0, (count + (chunk - 1)) // chunk, trip,
        [jnp.zeros((capacity + short,) + x.shape[1:], x.dtype)
         for x in arrays])
    return [s[:capacity] for s in staged] if short else staged


def snapshot_rows(arrays, at: jnp.ndarray, count: jnp.ndarray):
    """What a delta checkpoint takes of a table in the step's stream:
    :func:`read_rows` under its own stage, so nothing as long as a table
    array is made. ``at``'s length is a chunk or less, or a multiple of
    it."""
    return scope.stage("ckpt_gather")(read_rows)(list(arrays), at, count)


def read_distinct(weights: jnp.ndarray, at: jnp.ndarray, live: jnp.ndarray):
    """The row read of a pull that has its step's plan (``dedup.Plan``):
    ``weights[at]`` for the live slots of the distinct keys' buffer, zero
    rows for the others, by :func:`read_rows` over the buffer's occupied
    prefix. A batch's distinct keys fill a third of it (Criteo-shaped
    ids), and a duplicate is read once. ``(rows, slots walked)``."""
    oob = jnp.asarray(weights.shape[0], at.dtype)
    count = occupied_prefix(live)
    rows, = read_rows([weights], jnp.where(live, at, oob), count)
    chunk = min(APPLY_CHUNK, at.shape[0])
    return rows, (count + (chunk - 1)) // chunk * chunk


def record_pull(valid: jnp.ndarray, walked: jnp.ndarray, positions: int,
                record_stats: bool) -> None:
    """``pull_keys_live`` / ``pull_keys_walked`` / ``pull_positions`` of a
    pull that has a plan, under ``record_stats`` (the trace-time gate of
    ``alltoall.record_stat``): the distinct keys this shard resolved, the
    keys its chunks walked, the positions they were asked for."""
    record_stat("pull_keys_live", jnp.sum(valid, dtype=jnp.int32),
                record_stats)
    record_stat("pull_keys_walked", walked, record_stats)
    record_stat("pull_positions", jnp.int32(positions), record_stats)


def scatter_rows(arrays, at: jnp.ndarray, new):
    """Write the updated rows ``new`` back at ``at``, array by array;
    out-of-range entries (padding) are dropped. The write half of a sparse
    update."""
    @scope.stage("apply_scatter")
    def scatter(arrays, at, new):
        return [x.at[at].set(n, mode="drop") for x, n in zip(arrays, new)]

    return scatter(arrays, at, new)

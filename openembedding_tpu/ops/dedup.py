"""Static-shape index dedup + gradient combine.

The reference dedups indices client-side before every pull
(/root/reference/openembedding/server/EmbeddingPullOperator.cpp:60-84 via
EasyHashMap) and pre-sums duplicate-key gradients with counts before every
push (EmbeddingPushOperator.cpp:29-62, then MpscGradientReducer on the
server). Under XLA everything must be static-shape, so the TPU-native
equivalent is capacity-padded: ``jnp.unique(..., size=capacity)`` plus
scatter-add segment combines. Worst case capacity == batch size, so the
default is exact; callers may pass a smaller capacity based on measured batch
uniqueness (the reference measures this too: laboratory/benchmark/analyze.py).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
from flax import struct

from ..analysis import scope

# Padding sentinel for empty unique slots. Indices/keys are remapped away from
# this value by callers when the key space could include it.
FILL = jnp.iinfo(jnp.int32).min


@struct.dataclass
class Plan:
    """One id column's keys of one train step, deduplicated once, in front
    of the pull: what :func:`unique_indices` / :func:`unique_pairs` return,
    kept so that the pull resolves each distinct key once and expands by
    ``inverse``, and the push that follows combines its gradients by the
    same ``inverse`` into the same slots (``table.merge_gradients``,
    ``hash_table.merge_gradients``). The capacity is the number of keys,
    so no key overflows. It holds the keys as they come and nothing of a
    table: twin tables fed one column (a fused table and its ``:linear``
    column) pull and push through the SAME plan, each laying its own
    ownership mask over it (``EmbeddingCollection.plan``). What one
    table's pull found for the plan's slots travels beside it, a
    :class:`Resolution` a table. The routed exchange holds two of them a
    column and device (``alltoall.RoutedPlan``): of the slice it sends,
    and of the keys it receives as their owner."""

    uniq: jnp.ndarray       # [n] keys, [n, 2] wide ones; fill past the last
    inverse: jnp.ndarray    # [n]: uniq[inverse[i]] is key i
    valid: jnp.ndarray      # [n]: the slot holds a key, and not the fill
    # [n] int32, where the plan's maker has counted them: the positions
    # each slot stands for (the routed owner's: summed over the senders).
    # They are the column's, whatever the table: a push that finds them
    # here sums its gradients alone (:func:`combine_gradients`)
    counts: Optional[jnp.ndarray] = None


@struct.dataclass
class Resolution:
    """What ONE table's pull resolved for the slots of its step's
    :class:`Plan`, kept for the push of the same step, between which
    nothing writes the table: the push neither finds a key nor reads a
    weight row a second time. A shard's own, as it read them under its
    ownership mask (before the sum over the model axis, before the
    expansion by ``inverse``): on a mesh of several model shards every
    shard carries its part. Behind the routed exchange it is the owner's,
    of the plan of the keys it received (``alltoall.RoutedPlan.owner``):
    the rows as the owner read them, before they cross the wire."""

    # [n, dim]: the stored row of a key the table holds, the init row of a
    # hash key it does not (the row its insert writes), zeros for a slot
    # the shard does not own or that holds no key
    rows: jnp.ndarray
    # [n] int32, hash tables: the key's slot in the shard's key array,
    # -1 where it is not in the table, not owned or not valid (what
    # ``hash_table.find_or_insert``'s find phase would find again);
    # None for an array table, whose slot is the key
    slot: Optional[jnp.ndarray] = None


def plan_keys(keys: jnp.ndarray, fill_value: int = FILL) -> Plan:
    """The :class:`Plan` of a flat key stream ([n], or [n, 2] wide
    pairs)."""
    unique = unique_pairs if keys.ndim == 2 else unique_indices
    return Plan(*unique(keys, fill_value=fill_value))


def unique_indices(indices: jnp.ndarray, capacity: int | None = None,
                   fill_value: int = FILL
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Deduplicate a flat index vector into a fixed-capacity buffer.

    Returns ``(uniq [capacity], inverse [n], valid [capacity])`` where
    ``uniq[inverse[i]] == indices[i]`` and padding slots hold ``fill_value``.
    Equivalent of the reference's ``exb_unique_indices`` C-ABI helper
    (c_api.cc:220-231), reshaped for XLA: sorted, padded, mask instead of a
    dynamic length.

    CAUTION: if the batch holds more than ``capacity`` distinct indices, the
    overflow entries get ``inverse`` values >= capacity and their gradients
    are DROPPED by ``combine_gradients`` (scatter mode="drop"). The default
    capacity (== batch size) is always exact; only pass a smaller capacity if
    measured batch uniqueness guarantees it, and monitor with
    ``overflow_count``.
    """
    indices = indices.ravel()
    if capacity is None:
        capacity = indices.shape[0]

    @scope.stage("dedup")
    def unique(indices):
        fill = jnp.asarray(fill_value, dtype=indices.dtype)
        uniq, inverse = jnp.unique(indices, size=capacity, fill_value=fill,
                                   return_inverse=True)
        return uniq, inverse.ravel(), uniq != fill

    return unique(indices)


def count_keys(inverse: jnp.ndarray, capacity: int,
               in_counts: jnp.ndarray | None = None) -> jnp.ndarray:
    """:func:`combine_gradients`' counts alone ([capacity] int32): what a
    plan's maker keeps as ``Plan.counts``."""
    @scope.stage("dedup")
    def count(inverse, in_counts):
        add = jnp.int32(1) if in_counts is None \
            else in_counts.astype(jnp.int32)
        return jnp.zeros((capacity,), dtype=jnp.int32).at[inverse].add(
            add, mode="drop")

    return count(inverse, in_counts)


def combine_gradients(grads: jnp.ndarray, inverse: jnp.ndarray, capacity: int,
                      in_counts: jnp.ndarray | None = None,
                      counts: jnp.ndarray | None = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sum duplicate-key gradients into the unique buffer with counts.

    ``grads`` is [n, dim]; returns ``(summed [capacity, dim], counts
    [capacity])``. Matches the reference's client-side pre-reduce semantics:
    the optimizer sees the SUM over duplicates plus the duplicate count
    (EmbeddingPushOperator.cpp:29-62, MpscGradientReducer.h:27-54).

    ``in_counts`` carries per-entry multiplicities when the incoming grads are
    *already pre-reduced* (the owner side of the all-to-all exchange receives
    (sum, count) pairs from every peer and must SUM the counts) — the
    reference's server-side MpscGradientReducer merging client pre-reduces.
    ``counts`` are the combined counts where the caller holds them already
    (``Plan.counts``): the gradients alone are summed, and they come back.
    """
    n, dim = grads.shape
    if counts is not None:
        return scope.stage("dedup")(
            lambda grads, inverse: jnp.zeros(
                (capacity, dim), dtype=grads.dtype).at[inverse].add(
                    grads, mode="drop"))(grads, inverse), counts

    @scope.stage("dedup")
    def combine(grads, inverse, in_counts):
        summed = jnp.zeros((capacity, dim), dtype=grads.dtype).at[
            inverse].add(grads, mode="drop")
        add = jnp.int32(1) if in_counts is None \
            else in_counts.astype(jnp.int32)
        counts = jnp.zeros((capacity,), dtype=jnp.int32).at[inverse].add(
            add, mode="drop")
        return summed, counts

    return combine(grads, inverse, in_counts)


def overflow_count(inverse: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """Number of batch entries whose unique slot overflowed ``capacity``."""
    return jnp.sum(inverse >= capacity)


def unique_rows(rows: jnp.ndarray, capacity: int | None = None,
                fill_value: int = FILL
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Deduplicate composite keys: [n, K] integer rows, K >= 2.

    Generalizes :func:`unique_pairs` to any column count — wide (lo, hi)
    keys are K=2, the grouped exchange plane's table-tagged streams carry
    (key..., table_id) rows at K=2 or 3 (``parallel/grouped.py``). Rows
    are ranked lexicographically by K stable argsorts (minor column
    first, major column last — a stable sort by the major key preserves
    the minor order within equal majors), duplicates detected by
    adjacent-row equality, and compacted into a fixed-capacity buffer.
    Returns ``(uniq [capacity, K], inverse [n], valid [capacity])`` with
    padding rows equal to ``fill_value`` in every column. Matching
    :func:`unique_indices`'s contract, the sentinel group (padding rows,
    LAST column == fill) is NOT a valid unique.
    """
    n, k = rows.shape
    if capacity is None:
        capacity = n

    @scope.stage("dedup")
    def unique(rows):
        order = jnp.arange(n, dtype=jnp.int32)
        for c in range(k):
            order = order[jnp.argsort(rows[order, c], stable=True)]
        srt = rows[order]
        new_group = jnp.concatenate([
            jnp.ones((1,), bool),
            jnp.any(srt[1:] != srt[:-1], axis=1)])
        # group ordinal per sorted row -> unique slot; first of group
        # writes it
        slot_sorted = jnp.cumsum(new_group.astype(jnp.int32)) - 1
        inverse = jnp.zeros((n,), jnp.int32).at[order].set(slot_sorted)
        fill = jnp.asarray(fill_value, rows.dtype)
        uniq = jnp.full((capacity, k), fill, dtype=rows.dtype)
        dst = jnp.where(new_group, slot_sorted, capacity)
        uniq = uniq.at[dst].set(srt, mode="drop")
        valid = (jnp.arange(capacity) <= (slot_sorted[-1] if n else -1)) \
            & (uniq[:, -1] != fill)
        return uniq, inverse, valid

    return unique(rows)


def unique_pairs(pairs: jnp.ndarray, capacity: int | None = None,
                 fill_value: int = FILL
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Deduplicate WIDE keys: [n, 2] int32 (lo, hi) rows, x64-off.

    The 64-bit twin of :func:`unique_indices` for processes without
    ``jax_enable_x64`` (a jnp int64 pack is unavailable there); the
    K-column generalization lives in :func:`unique_rows`. Returns
    ``(uniq [capacity, 2], inverse [n], valid [capacity])`` with padding
    rows equal to ``(fill_value, fill_value)``.
    """
    return unique_rows(pairs, capacity, fill_value)

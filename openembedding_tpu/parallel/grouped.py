"""Grouped multi-table exchange plane: one collective round per GROUP, not
per table (``plane="a2a+grouped"``).

The reference pays one pull RPC fan-out per PS variable per batch (SURVEY
§3.2) and the per-table translation inherits exactly that cost on TPU:
``EmbeddingCollection.pull`` / ``apply_gradients`` loop over specs, so a
model with T heterogeneous tables launches T independent dedup + bucketize
+ all-to-all + gather pipelines per step. ``fused.py`` rescues the
homogeneous case (same dim, same config -> literally one table); this
module is the heterogeneous counterpart — DLRM/FBGEMM-style table
batching — and stays EXACTLY equivalent to the per-table loop:

* a static planner groups the collection's grouped-plane tables by
  (dim-bucket, array/hash, key width, layout, shard count, dtype);
* each group's key streams are concatenated into ONE table-id-tagged
  index stream with static per-table segment offsets
  (``alltoall.segment_offsets``): array tables reuse the fused-table
  offset math (table t's id i rides as ``base[t] + i`` over the disjoint
  concatenation of padded vocabs — cf. ``fused.FusedMapper.offsets``),
  hash tables carry an explicit table-id column next to the key words
  (``[n, 2]`` int32 ``(key, tag)`` / ``[n, 3]`` ``(lo, hi, tag)`` rows,
  deduped lexicographically by ``ops.dedup.unique_rows``);
* ONE ``alltoall.exchange_pull`` (and one pre-reduced ``exchange_push``)
  routes the whole group per step. The owner carves the stream back into
  per-table rows on device (tag/offset dispatch is local index math) and
  applies each table's OWN optimizer server-side, so results match the
  per-table loop bit-for-bit up to float summation order.

Rows travel at the group's bucket dim (next power of two over member
dims); each table's ``dim_t`` columns are sliced back out after the
exchange — mixed dims share a round at the cost of column padding, the
standard table-batched-embedding trade.

On the owner, per-table dispatch over the received stream is WINDOWED,
not full-stream: the stream is sorted once by table tag (array offsets
sort tables contiguously by construction; sentinels are int32 min and
sort first), and each table gathers/probes/scatters only a
``dynamic_slice`` window of statically-bounded size — a single owner
can receive at most a table's global pre-dedup entry count, a
trace-time constant — so the owner-side work is O(stream · log), not
O(num_tables · stream). Without this, a 52-table group pays ~52x the
per-table loop's gather+scatter flops and the collective-launch win
drowns (measured: grouped push 8x the per-table wall on cpu8).

Equivalence argument, briefly: tagged keys from different tables are
distinct by construction (disjoint offset ranges / distinct tag columns),
so the group-level dedup merges exactly the duplicates the per-table
dedups merged; the exchange is exact for any key distribution (residue
rounds / overflow fallback, see ``alltoall.py``); and the owner applies
each table's optimizer once per key with the same merged (grad sum,
count) pre-reduces. Only the float ADD ORDER of duplicate-gradient
combines may differ — the same caveat the hot-row cache plane carries.

Per-table entry points (serving probes, the checkpoint loader,
``pull_sharded`` on a single grouped spec) fall back to the plain
``"a2a"`` program — grouping exists only at the collection level, so the
plane composes freely with ``"a2a+cache"`` variables in the same model
(cached tables keep their own replica path; grouped tables batch).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .. import hash_table as hash_lib
from .. import table as table_lib
from ..analysis import scope
from ..ops import dedup
from ..utils import observability
from . import alltoall as a2a

GROUPED_PLANE = "a2a+grouped"

# array offset streams are int32: a group's concatenated padded vocabs
# must stay addressable (the planner splits groups at this boundary)
_MAX_OFFSET_SPAN = 2**31 - 1


def dim_bucket(dim: int) -> int:
    """Rows travel at the next power of two >= dim (min 1): mixed dims
    share one exchange round at the cost of column padding."""
    return 1 << max(0, int(dim) - 1).bit_length() if dim > 1 else 1


@dataclasses.dataclass(frozen=True)
class ArrayMember:
    """Static per-table facts one array-table group member contributes."""

    name: str
    dim: int
    spec: Any                     # sharded_table.ShardingSpec
    optimizer: Any                # SparseOptimizer (push only)
    slot_names: Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class HashMember:
    """Static per-table facts one hash-table group member contributes."""

    name: str
    dim: int
    spec: Any                     # sharded_hash.HashShardingSpec
    optimizer: Any
    initializer: Any              # None = read-only pull contract
    slot_names: Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class GroupPlan:
    """One exchange group: every member shares bucket dim, kind, key
    shape, shard count and mesh axes, so one routed round serves all."""

    kind: str                     # "array" | "hash"
    bucket_dim: int
    key_dtype: str                # array: "int32" offsets; hash: key words
    members: tuple
    bases: Tuple[int, ...] = ()   # array only: fused-style offset bases

    @property
    def wide(self) -> bool:
        return self.kind == "hash" and self.key_dtype == "wide"


def plan_groups(collection, names, *, read_only: bool = False
                ) -> Tuple[GroupPlan, ...]:
    """Partition ``names`` (all on the grouped plane) into exchange groups.

    Grouping key: (kind, dim bucket, key shape, shard count, layout, mesh
    axes, exchange sizing, storage dtype) — everything that must agree for
    the streams to share one routed round. Members keep registration
    order; array groups split when the concatenated padded vocabs would
    overflow the int32 offset space.
    """
    ordered = sorted(names, key=collection.variable_id)
    buckets: Dict[tuple, list] = {}
    for name in ordered:
        spec = collection.specs[name]
        ss = collection.sharding_spec(name)
        if ss.plane != GROUPED_PLANE:
            raise ValueError(f"{name!r} is not on the grouped plane "
                             f"({GROUPED_PLANE!r})")
        if spec.use_hash:
            key = ("hash", spec.key_dtype,
                   dim_bucket(spec.output_dim),
                   ss.num_shards, ss.data_axis, ss.model_axis,
                   ss.a2a_capacity, ss.a2a_slack, spec.dtype,
                   ss.exchange_precision, ss.push_precision)
        else:
            key = ("array", dim_bucket(spec.output_dim),
                   ss.num_shards,
                   ss.layout, ss.data_axis, ss.model_axis,
                   ss.a2a_capacity, ss.a2a_slack, spec.dtype,
                   ss.exchange_precision, ss.push_precision)
        buckets.setdefault(key, []).append(name)

    plans = []
    for key, group_names in buckets.items():
        if key[0] == "hash":
            members = tuple(
                HashMember(
                    name=n, dim=collection.specs[n].output_dim,
                    spec=collection.sharding_spec(n),
                    optimizer=collection.optimizer(n),
                    initializer=(None if read_only
                                 else collection.initializer(n)),
                    slot_names=tuple(collection.optimizer(n).slot_shapes(
                        collection.specs[n].output_dim)))
                for n in group_names)
            plans.append(GroupPlan(kind="hash", bucket_dim=key[2],
                                   key_dtype=key[1], members=members))
            continue
        # array: accumulate members until the offset space would overflow
        run, span = [], 0
        for n in group_names:
            ss = collection.sharding_spec(n)
            if run and span + ss.padded_vocab > _MAX_OFFSET_SPAN:
                plans.append(_array_plan(collection, tuple(run), key[1]))
                run, span = [], 0
            run.append(n)
            span += ss.padded_vocab
        if run:
            plans.append(_array_plan(collection, tuple(run), key[1]))
    plans.sort(key=lambda p: collection.variable_id(p.members[0].name))
    return tuple(plans)


def _array_plan(collection, group_names, bucket: int) -> GroupPlan:
    members = tuple(
        ArrayMember(name=n, dim=collection.specs[n].output_dim,
                    spec=collection.sharding_spec(n),
                    optimizer=collection.optimizer(n),
                    slot_names=tuple(collection.optimizer(n).slot_shapes(
                        collection.specs[n].output_dim)))
        for n in group_names)
    bases = a2a.segment_offsets([m.spec.padded_vocab for m in members])
    return GroupPlan(kind="array", bucket_dim=bucket, key_dtype="int32",
                     members=members, bases=bases)


def _stream_bounds(plan: GroupPlan, idxs, grid_sizes, split_sizes
                   ) -> Tuple[int, ...]:
    """Static per-table caps on the entries ONE owner can receive for one
    table in one exchange. The senders jointly hold every data-row's
    stream exactly once (split peers partition it; per-sender dedup only
    shrinks it), so table t contributes at most its global pre-dedup
    entry count: data_rows * its per-device entries — a trace-time
    constant, which makes the owner-side per-table windows static."""
    data_rows = math.prod(grid_sizes) // math.prod(split_sizes)
    out = []
    for t in range(len(plan.members)):
        if plan.kind == "hash" and plan.wide:
            n_local = idxs[t].reshape(-1, 2).shape[0]
        else:
            n_local = idxs[t].ravel().shape[0]
        out.append(n_local * data_rows)
    return tuple(out)


def _window(start, size: int, *streams):
    """``dynamic_slice`` window [start, start+size) of each 1/2-D stream
    (start pre-clamped by the caller)."""
    return tuple(
        lax.dynamic_slice_in_dim(s, start, size, axis=0) for s in streams)


def _sorted_member_windows(col, bounds, thresholds, *streams):
    """Sorted-window dispatch core: ONE argsort of ``col`` (array offset
    keys / hash tag column — sentinels are int min and sort first, each
    member's rows land contiguous), then per member the
    statically-bounded window ``[start, start + min(n, bounds[t]))``
    with ``start = clamp(searchsorted(col_sorted, thresholds[t]))``.
    Yields ``(t, (col_w, order_w, *stream_w))`` — ``order_w`` maps
    window positions back to un-sorted stream positions (pull's
    scatter-back). The clamp keeps windows in range; spilling into a
    neighbor's rows is harmless because every caller masks foreign rows
    (disjoint offset ranges / distinct tags) before touching state, so
    overlapping windows contribute exact zeros outside their member."""
    n = col.shape[0]
    order = jnp.argsort(col)
    sorted_all = (col[order], order) + tuple(s[order] for s in streams)
    for t, (bound, thr) in enumerate(zip(bounds, thresholds)):
        size = min(n, bound)
        start = jnp.minimum(
            jnp.searchsorted(sorted_all[0],
                             jnp.asarray(thr, col.dtype)
                             ).astype(jnp.int32),
            jnp.int32(n - size))
        yield t, _window(start, size, *sorted_all)


# --- array groups: fused-style offset streams --------------------------------

def _array_owner_resolve(plan: GroupPlan, me):
    """(owner_fn, resolve_builder) over an offset-tagged array stream."""
    members = plan.members
    bases = plan.bases
    num_shards = members[0].spec.num_shards

    def owner(keys):
        own = jnp.full(keys.shape, num_shards, jnp.int32)
        for t, m in enumerate(members):
            in_t = (keys >= bases[t]) & (keys < bases[t + 1])
            shard, _ = m.spec.shard_and_local(keys - bases[t])
            own = jnp.where(in_t, shard.astype(jnp.int32), own)
        return own

    def resolve_with(weights, bounds):
        def resolve(keys):
            out = jnp.zeros((keys.shape[0], plan.bucket_dim),
                            weights[0].dtype)
            for t, (kw, ow) in _sorted_member_windows(
                    keys, bounds, bases[:-1]):
                m = members[t]
                shard, local = m.spec.shard_and_local(kw - bases[t])
                mine = ((kw >= bases[t]) & (kw < bases[t + 1])
                        & (shard == me))
                rows = jnp.take(weights[t], jnp.where(mine, local, 0),
                                axis=0, mode="clip")
                rows = jnp.where(mine[:, None], rows,
                                 jnp.zeros_like(rows))
                out = out.at[ow].add(jnp.pad(
                    rows, ((0, 0), (0, plan.bucket_dim - m.dim))))
            return out
        return resolve

    return owner, resolve_with


def _tag_array_streams(plan: GroupPlan, idxs) -> jnp.ndarray:
    """Per-table id columns -> one offset-tagged int32 stream. Ids a table
    would reject (negative / beyond its padded vocab) are masked to the
    sentinel BEFORE the offset shift so they can never alias into a
    neighbor table's range."""
    tagged = []
    for t, m in enumerate(plan.members):
        flat = idxs[t].ravel()
        ok = (flat >= 0) & (flat < m.spec.padded_vocab)
        safe = jnp.where(ok, flat, 0).astype(jnp.int32)
        tagged.append(jnp.where(ok, safe + jnp.int32(plan.bases[t]),
                                jnp.int32(dedup.FILL)))
    return jnp.concatenate(tagged)


@functools.lru_cache(maxsize=None)
def _array_pull_program(mesh: Mesh, plan: GroupPlan, batch_sharded: bool,
                        record_stats: bool = False):
    members = plan.members
    first = members[0].spec
    T = len(members)
    grid_axes, grid_sizes, split_axes, split_sizes = a2a.grid_info(
        mesh, first.shard_axes, first.model_axis, batch_sharded)
    batch_spec = P(first.data_axis) if batch_sharded else P()

    def _pull(*args):
        weights, idxs = args[:T], args[T:]
        me = a2a.linear_shard_id(grid_axes, grid_sizes)
        owner, resolve_with = _array_owner_resolve(plan, me)
        flat_all = _tag_array_streams(plan, idxs)
        bounds = _stream_bounds(plan, idxs, grid_sizes, split_sizes)
        rows = a2a.exchange_pull(
            flat_all, resolve_with(weights, bounds), owner,
            sentinel=dedup.FILL,
            dim=plan.bucket_dim, num_shards=first.num_shards,
            grid_axes=grid_axes, grid_sizes=grid_sizes,
            split_axes=split_axes, split_sizes=split_sizes,
            capacity=first.a2a_capacity, slack=first.a2a_slack,
            record_stats=record_stats,
            wire_dtype=first.pull_wire_dtype)
        segs = a2a.carve_segments(rows,
                                  [i.ravel().shape[0] for i in idxs])
        return tuple(
            seg[:, :m.dim].reshape(idxs[t].shape + (m.dim,))
            for t, (seg, m) in enumerate(zip(segs, members)))

    _pull.__name__ = "grouped_pull"
    fn = shard_map(_pull, mesh=mesh,
                   in_specs=(first.row_spec(),) * T + (batch_spec,) * T,
                   out_specs=(batch_spec,) * T,
                   check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _array_push_program(mesh: Mesh, plan: GroupPlan, batch_sharded: bool,
                        record_stats: bool = False):
    members = plan.members
    first = members[0].spec
    T = len(members)
    grid_axes, grid_sizes, split_axes, split_sizes = a2a.grid_info(
        mesh, first.shard_axes, first.model_axis, batch_sharded)
    batch_spec = P(first.data_axis) if batch_sharded else P()

    def _apply(*args):
        weights = args[:T]
        slots = args[T:2 * T]
        idxs = args[2 * T:3 * T]
        grads = args[3 * T:]
        me = a2a.linear_shard_id(grid_axes, grid_sizes)
        owner, _ = _array_owner_resolve(plan, me)
        flat_all = _tag_array_streams(plan, idxs)
        bounds = _stream_bounds(plan, idxs, grid_sizes, split_sizes)
        g_all = jnp.concatenate([
            jnp.pad(grads[t].reshape(-1, m.dim),
                    ((0, 0), (0, plan.bucket_dim - m.dim)))
            for t, m in enumerate(members)])

        def merge_fn(keys, g, counts):
            merged = []
            for t, (kw, _ow, gw, cw) in _sorted_member_windows(
                    keys, bounds, plan.bases[:-1], g, counts):
                m = members[t]
                shard, local = m.spec.shard_and_local(
                    kw - plan.bases[t])
                mine = ((kw >= plan.bases[t])
                        & (kw < plan.bases[t + 1]) & (shard == me))
                merged.append(table_lib.merge_gradients(
                    jnp.where(mine, local, -1), gw[:, :m.dim],
                    in_counts=cw))
            return tuple(merged)

        merged = a2a.exchange_push(
            flat_all, g_all, merge_fn, owner, sentinel=dedup.FILL,
            num_shards=first.num_shards, grid_axes=grid_axes,
            grid_sizes=grid_sizes, split_axes=split_axes,
            split_sizes=split_sizes, capacity=first.a2a_capacity,
            slack=first.a2a_slack, record_stats=record_stats,
            wire_dtype=first.push_wire_dtype)
        return tuple(
            table_lib.apply_rows(weights[t], slots[t], m.optimizer,
                                 *merged[t], record_stats=record_stats)
            for t, m in enumerate(members))

    _apply.__name__ = "grouped_push"
    row = first.row_spec()
    slot_specs = tuple({name: row for name in m.slot_names}
                       for m in members)
    fn = shard_map(_apply, mesh=mesh,
                   in_specs=(row,) * T + slot_specs
                   + (batch_spec,) * 2 * T,
                   out_specs=tuple((row, slot_specs[t])
                                   for t in range(T)),
                   check_vma=False)
    return jax.jit(fn)


# --- hash groups: explicit table-id column next to the key words -------------

def _hash_key_dtype(plan: GroupPlan):
    return jnp.int32 if plan.wide else jnp.dtype(plan.key_dtype)


def _tag_hash_streams(plan: GroupPlan, idxs) -> jnp.ndarray:
    """Per-table key columns -> one [N, kw+1] (key..., tag) stream.
    Invalid keys (EMPTY sentinel) become all-sentinel rows, so their tag
    never marks them as any table's traffic."""
    empty = hash_lib.empty_key(_hash_key_dtype(plan))
    tagged = []
    for t, m in enumerate(plan.members):
        if plan.wide:
            flat = idxs[t].reshape(-1, 2)
            valid = flat[:, 1] != empty
            cols = flat
        else:
            flat = idxs[t].ravel()
            valid = flat != empty
            cols = flat[:, None]
        tag = jnp.where(valid, jnp.asarray(t, cols.dtype),
                        jnp.asarray(empty, cols.dtype))
        row = jnp.concatenate(
            [jnp.where(valid[:, None], cols,
                       jnp.asarray(empty, cols.dtype)), tag[:, None]],
            axis=1)
        tagged.append(row)
    return jnp.concatenate(tagged)


def _hash_owner(plan: GroupPlan, kw: int):
    members = plan.members
    num_shards = members[0].spec.num_shards

    def owner(q):
        keyc = q[:, :kw] if plan.wide else q[:, 0]
        tag = q[:, kw]
        valid = (tag >= 0) & (tag < len(members))
        own = members[0].spec.owner_shard(keyc)
        return jnp.where(valid, own,
                         jnp.int32(num_shards)).astype(jnp.int32)

    return owner


@functools.lru_cache(maxsize=None)
def _hash_pull_program(mesh: Mesh, plan: GroupPlan, batch_sharded: bool,
                       record_stats: bool = False):
    members = plan.members
    first = members[0].spec
    T = len(members)
    kw = 2 if plan.wide else 1
    grid_axes, grid_sizes, split_axes, split_sizes = a2a.grid_info(
        mesh, first.shard_axes, first.model_axis, batch_sharded)
    batch_spec = P(first.data_axis) if batch_sharded else P()
    empty = hash_lib.empty_key(_hash_key_dtype(plan))

    def _pull(*args):
        tkeys = args[:T]
        tweights = args[T:2 * T]
        rngs = args[2 * T:3 * T]
        idxs = args[3 * T:]
        me = a2a.linear_shard_id(grid_axes, grid_sizes)
        flat_all = _tag_hash_streams(plan, idxs)
        owner = _hash_owner(plan, kw)
        bounds = _stream_bounds(plan, idxs, grid_sizes, split_sizes)

        def resolve(q):
            keyc_all = q[:, :kw] if plan.wide else q[:, 0]
            out = jnp.zeros((q.shape[0], plan.bucket_dim),
                            tweights[0].dtype)
            for t, (tag, ow, keyc) in _sorted_member_windows(
                    q[:, kw], bounds, range(T), keyc_all):
                m = members[t]
                mine = (tag == t) & (m.spec.owner_shard(keyc) == me)
                if plan.wide:
                    masked = jnp.where(mine[:, None], keyc,
                                       jnp.asarray(empty, keyc.dtype))
                else:
                    masked = jnp.where(mine, keyc,
                                       jnp.asarray(empty, keyc.dtype))
                local = hash_lib.HashTableState(
                    keys=tkeys[t], weights=tweights[t], slots={},
                    init_rng=rngs[t],
                    insert_failures=jnp.zeros((), jnp.int32))
                rows = hash_lib.pull(local, masked, m.initializer,
                                     max_probes=m.spec.max_probes)
                out = out.at[ow].add(jnp.pad(
                    rows, ((0, 0), (0, plan.bucket_dim - m.dim))))
            return out

        rows = a2a.exchange_pull(
            flat_all, resolve, owner, sentinel=empty,
            dim=plan.bucket_dim, num_shards=first.num_shards,
            grid_axes=grid_axes, grid_sizes=grid_sizes,
            split_axes=split_axes, split_sizes=split_sizes,
            capacity=first.a2a_capacity, slack=first.a2a_slack,
            record_stats=record_stats,
            wire_dtype=first.pull_wire_dtype)
        sizes = [(i.reshape(-1, 2) if plan.wide else i.ravel()).shape[0]
                 for i in idxs]
        segs = a2a.carve_segments(rows, sizes)
        outs = []
        for t, (seg, m) in enumerate(zip(segs, members)):
            shape = (idxs[t].shape[:-1] if plan.wide else idxs[t].shape) \
                + (m.dim,)
            outs.append(seg[:, :m.dim].reshape(shape))
        return tuple(outs)

    _pull.__name__ = "grouped_hash_pull"
    row = first.row_spec()
    fn = shard_map(_pull, mesh=mesh,
                   in_specs=(row,) * 2 * T + (P(),) * T
                   + (batch_spec,) * T,
                   out_specs=(batch_spec,) * T,
                   check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _hash_push_program(mesh: Mesh, plan: GroupPlan, batch_sharded: bool,
                       record_stats: bool = False):
    members = plan.members
    first = members[0].spec
    T = len(members)
    kw = 2 if plan.wide else 1
    grid_axes, grid_sizes, split_axes, split_sizes = a2a.grid_info(
        mesh, first.shard_axes, first.model_axis, batch_sharded)
    batch_spec = P(first.data_axis) if batch_sharded else P()
    empty = hash_lib.empty_key(_hash_key_dtype(plan))

    def _apply(*args):
        tkeys = args[:T]
        tweights = args[T:2 * T]
        tslots = args[2 * T:3 * T]
        rngs = args[3 * T:4 * T]
        idxs = args[4 * T:5 * T]
        grads = args[5 * T:]
        me = a2a.linear_shard_id(grid_axes, grid_sizes)
        flat_all = _tag_hash_streams(plan, idxs)
        owner = _hash_owner(plan, kw)
        bounds = _stream_bounds(plan, idxs, grid_sizes, split_sizes)
        g_all = jnp.concatenate([
            jnp.pad(grads[t].reshape(-1, m.dim),
                    ((0, 0), (0, plan.bucket_dim - m.dim)))
            for t, m in enumerate(members)])

        tables = [hash_lib.HashTableState(
            keys=tkeys[t], weights=tweights[t], slots=tslots[t],
            init_rng=rngs[t], insert_failures=jnp.zeros((), jnp.int32))
            for t in range(T)]

        # the branches of the push combine and write nothing; each table's
        # find-or-insert and apply follow the exchange (alltoall.
        # exchange_push: a table inside the conditional is copied)
        def merge_fn(q, g, counts):
            keyc_all = q[:, :kw] if plan.wide else q[:, 0]
            merged = []
            for t, (tag, _ow, keyc, gw, cw) in _sorted_member_windows(
                    q[:, kw], bounds, range(T), keyc_all, g, counts):
                m = members[t]
                mine = (tag == t) & (m.spec.owner_shard(keyc) == me)
                if plan.wide:
                    masked = jnp.where(mine[:, None], keyc,
                                       jnp.asarray(empty, keyc.dtype))
                else:
                    masked = jnp.where(mine, keyc,
                                       jnp.asarray(empty, keyc.dtype))
                merged.append(hash_lib.combine_keys(
                    tables[t], masked, gw[:, :m.dim], in_counts=cw))
            return tuple(merged)

        merged = a2a.exchange_push(
            flat_all, g_all, merge_fn, owner, sentinel=empty,
            num_shards=first.num_shards, grid_axes=grid_axes,
            grid_sizes=grid_sizes, split_axes=split_axes,
            split_sizes=split_sizes, capacity=first.a2a_capacity,
            slack=first.a2a_slack, record_stats=record_stats,
            wire_dtype=first.push_wire_dtype)
        out = []
        for t, m in enumerate(members):
            uniq, valid, summed, counts = merged[t]
            k_t, failed, slot, inserted, fresh = hash_lib.place_keys(
                tables[t], m.initializer, uniq, valid,
                max_probes=m.spec.max_probes, record_stats=record_stats)
            # per-shard failure deltas -> replicated global totals
            out.append((k_t, *table_lib.apply_rows(
                tweights[t], tslots[t], m.optimizer, slot,
                valid & (slot >= 0), summed, counts, fresh, inserted,
                record_stats=record_stats),
                lax.psum(failed, first.shard_axes)))
        return tuple(out)

    _apply.__name__ = "grouped_hash_push"
    row = first.row_spec()
    slot_specs = tuple({name: row for name in m.slot_names}
                       for m in members)
    fn = shard_map(_apply, mesh=mesh,
                   in_specs=(row,) * 2 * T + slot_specs + (P(),) * T
                   + (batch_spec,) * 2 * T,
                   out_specs=tuple((row, row, slot_specs[t], P())
                                   for t in range(T)),
                   check_vma=False)
    return jax.jit(fn)


# --- collection-level dispatch -----------------------------------------------

def _record_group(plan: GroupPlan, idxs, itemsize: int) -> None:
    """Gated host counters: groups exchanged + an entry-granularity
    (pre-dedup) byte estimate of the group's routed traffic."""
    if plan.kind == "hash":
        kc = (2 if plan.wide else 1) + 1
        n = sum(int(i.size) // (2 if plan.wide else 1) for i in idxs)
    else:
        kc = 1
        n = sum(int(i.size) for i in idxs)
    nbytes = n * (plan.bucket_dim * itemsize + kc * 4)
    observability.GLOBAL.add("grouped_groups", 1)
    observability.GLOBAL.add("grouped_exchange_bytes", nbytes)
    # distribution next to the sum: the histogram separates "one huge
    # group" from "many small ones" — the sum alone cannot
    scope.HISTOGRAMS.observe("grouped_exchange_bytes", float(nbytes))


def pull_grouped(collection, states, idx_map: Dict[str, jnp.ndarray], *,
                 read_only: bool = False,
                 batch_sharded: bool = True) -> Dict[str, jnp.ndarray]:
    """Lookup rows for every grouped-plane column in ``idx_map`` — one
    routed exchange per GROUP. Called by ``EmbeddingCollection.pull``;
    returns raw (un-pooled) rows shaped like the per-table path's."""
    record = observability.evaluate_performance()
    # the in-program residue counters (record -> jax.debug.callback) fire
    # per step even under an outer jit; the HOST counters here run once
    # per COMPILE there, so they record only on eager dispatch
    host_record = record and not observability.under_trace(idx_map)
    mesh = collection.mesh
    out = {}
    for plan in plan_groups(collection, tuple(idx_map),
                            read_only=read_only):
        names = [m.name for m in plan.members]
        idxs = [idx_map[n] for n in names]
        if plan.kind == "array":
            fn = _array_pull_program(mesh, plan, batch_sharded, record)
            args = [states[n].weights for n in names] + idxs
        else:
            fn = _hash_pull_program(mesh, plan, batch_sharded, record)
            args = ([states[n].keys for n in names]
                    + [states[n].weights for n in names]
                    + [states[n].init_rng for n in names] + idxs)
        res = observability.plane_timed(
            "pull", plan.members[0].spec.plane_label, record, fn, *args)
        if host_record:
            _record_group(plan, idxs,
                          states[names[0]].weights.dtype.itemsize)
        out.update(zip(names, res))
    return out


def apply_gradients_grouped(collection, states,
                            idx_map: Dict[str, jnp.ndarray],
                            grads_map: Dict[str, jnp.ndarray], *,
                            batch_sharded: bool = True) -> Dict[str, Any]:
    """Push+update for every grouped-plane column — one pre-reduced
    routed exchange per GROUP, per-table optimizers applied server-side.
    Returns the new state per variable (same pytree types as the
    per-table path)."""
    record = observability.evaluate_performance()
    host_record = record and not observability.under_trace(idx_map)
    mesh = collection.mesh
    out = {}
    for plan in plan_groups(collection, tuple(idx_map)):
        names = [m.name for m in plan.members]
        idxs = [idx_map[n] for n in names]
        grads = [grads_map[n] for n in names]
        if plan.kind == "array":
            fn = _array_push_program(mesh, plan, batch_sharded, record)
            res = observability.plane_timed(
                "push", plan.members[0].spec.plane_label, record, fn,
                *([states[n].weights for n in names]
                  + [states[n].slots for n in names] + idxs + grads))
            for n, (w, s) in zip(names, res):
                out[n] = table_lib.TableState(weights=w, slots=s)
        else:
            fn = _hash_push_program(mesh, plan, batch_sharded, record)
            res = observability.plane_timed(
                "push", plan.members[0].spec.plane_label, record, fn,
                *([states[n].keys for n in names]
                  + [states[n].weights for n in names]
                  + [states[n].slots for n in names]
                  + [states[n].init_rng for n in names] + idxs + grads))
            for n, (k, w, s, f) in zip(names, res):
                out[n] = hash_lib.HashTableState(
                    keys=k, weights=w, slots=s,
                    init_rng=states[n].init_rng,
                    insert_failures=states[n].insert_failures + f)
        if host_record:
            _record_group(plan, idxs,
                          out[names[0]].weights.dtype.itemsize)
    return out

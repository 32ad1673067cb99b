"""Hash-table embeddings sharded over the device mesh.

Same two data planes as ``sharded_table`` but for unbounded key spaces:

* ``"a2a"`` (default) — owner-routed exchange over the whole mesh (see
  ``parallel/alltoall.py``): each device owns one open-addressing shard,
  keys are partitioned ``key % num_shards`` (the reference's modulo shard
  layout, /root/reference/openembedding/server/EmbeddingPullOperator.cpp:73-78,
  applied to hashed keys, which are uniform by construction) and routed to
  their single owner.
* ``"psum"`` — shards along the model axis only (replicated over data):
  non-owned keys are masked to the EMPTY sentinel before the local table
  call (zero pull rows / dropped updates), so a psum over the model axis
  reconstructs the full batch exactly once.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..analysis import scope
from ..meta import EmbeddingVariableMeta
from ..utils import observability
from ..optim.initializers import make_initializer
from ..optim.optimizers import SparseOptimizer, make_optimizer
from .. import hash_table as hash_lib
from .. import table as table_lib
from . import alltoall as a2a
from . import hot_cache
from . import precision
from . import sharded_table as st
from .mesh import DATA_AXIS, MODEL_AXIS


@dataclasses.dataclass(frozen=True)
class HashShardingSpec:
    """Static layout of one hash table over the mesh."""

    num_shards: int
    capacity_per_shard: int
    max_probes: int = hash_lib.DEFAULT_MAX_PROBES
    data_axis: str = DATA_AXIS
    model_axis: str = MODEL_AXIS
    plane: str = "a2a"   # sharded_table.PLANES member
    a2a_capacity: int = 0
    a2a_slack: float = 2.0
    key_width: int = 32  # 64 = [n, 2] int32 (lo, hi) pairs, x64-off
    cache_k: int = 0     # hot-row replica slots ("a2a+cache" plane)
    # compressed-exchange rungs (parallel/precision.py)
    exchange_precision: str = "f32"   # "f32" | "bf16"
    push_precision: str = "f32"       # "f32" | "bf16" | "int8_ef"

    @property
    def is_cached(self) -> bool:
        return self.plane == "a2a+cache"

    @property
    def plane_label(self) -> str:
        """Observable plane token incl. the precision suffix."""
        return precision.plane_label(self.plane, self.exchange_precision,
                                     self.push_precision)

    @property
    def pull_wire_dtype(self):
        return precision.wire_dtype(self.exchange_precision)

    @property
    def push_wire_dtype(self):
        return precision.wire_dtype(self.push_precision) \
            if self.push_precision == "bf16" else None

    @property
    def is_int8_ef(self) -> bool:
        return self.push_precision == "int8_ef"

    @property
    def is_grouped(self) -> bool:
        """Collection-level multi-table exchange (``parallel/grouped.py``)."""
        return self.plane in ("a2a+grouped", "a2a+grouped+pipelined")

    @property
    def is_pipelined(self) -> bool:
        """Trainer-level double-buffered exchange schedule
        (``parallel/pipelined.py``)."""
        return self.plane in ("a2a+pipelined", "a2a+grouped+pipelined")

    @property
    def shard_axes(self) -> tuple:
        if self.plane != "psum":
            return (self.data_axis, self.model_axis)
        return (self.model_axis,)

    @property
    def wide(self) -> bool:
        return self.key_width == 64

    def row_spec(self) -> P:
        return P(self.shard_axes)

    def owner_shard(self, keys: jnp.ndarray) -> jnp.ndarray:
        if hash_lib.is_wide(keys):
            # unsigned 64-bit key mod S computed in 32-bit arithmetic
            # (x64-off): (hi * 2^32 + lo) mod S with 2^32 mod S folded in.
            # Safe while S < 2^16 (S^2 fits uint32) — far beyond any mesh.
            s = self.num_shards
            c = jnp.uint32((1 << 32) % s)
            lo = keys[:, 0].astype(jnp.uint32)
            hi = keys[:, 1].astype(jnp.uint32)
            return (((hi % s) * c + lo % s) % s).astype(jnp.int32)
        # unsigned mod so negative (but valid) hashed keys still land on a
        # deterministic shard; jnp % already yields non-negative for positive
        # divisors, the cast keeps int64/int32 behavior identical.
        return (keys % jnp.asarray(self.num_shards, keys.dtype)).astype(jnp.int32)


def make_hash_sharding_spec(mesh: Mesh, total_capacity: int,
                            num_shards: int = -1,
                            max_probes: int = hash_lib.DEFAULT_MAX_PROBES,
                            plane: str = "a2a",
                            a2a_capacity: int = 0,
                            a2a_slack: float = 2.0,
                            key_width: int = 32,
                            cache_k: int = 0,
                            exchange_precision: str = "f32",
                            push_precision: str = "f32"
                            ) -> HashShardingSpec:
    """num_shards=-1 => one shard per device ("a2a") / per model slice ("psum").

    ``plane="a2a+cache"``: a2a layout plus a ``cache_k``-row hot-row replica
    on every device (``parallel/hot_cache.py``); 0 picks the default size.
    A ``+bf16``/``+int8`` plane suffix selects the compressed-exchange
    rungs (``parallel/precision.py``).
    """
    plane, exchange_precision, push_precision = st._resolve_precision(
        plane, exchange_precision, push_precision)
    if plane not in st.PLANES:
        raise ValueError(f"unknown plane {plane!r}")
    if key_width not in (32, 64):
        raise ValueError(f"key_width must be 32 or 64, got {key_width}")
    want = mesh.shape[MODEL_AXIS] if plane == "psum" else mesh.size
    if num_shards == -1:
        num_shards = want
    if num_shards != want:
        raise ValueError(
            f"num_shards={num_shards} must equal the {plane}-plane shard "
            f"count {want} for this mesh (or pass -1)")
    if plane == "a2a+cache" and cache_k <= 0:
        cache_k = hot_cache.DEFAULT_CACHE_K
    if plane != "a2a+cache":
        cache_k = 0
    cap = hash_lib.round_capacity(-(-total_capacity // num_shards))
    return HashShardingSpec(num_shards=num_shards, capacity_per_shard=cap,
                            max_probes=max_probes, plane=plane,
                            a2a_capacity=a2a_capacity, a2a_slack=a2a_slack,
                            key_width=key_width, cache_k=cache_k,
                            exchange_precision=exchange_precision,
                            push_precision=push_precision)


def table_state_specs(optimizer: SparseOptimizer, dim: int,
                      spec: HashShardingSpec):
    row = spec.row_spec()
    return hash_lib.HashTableState(
        keys=row, weights=row,
        slots={name: row for name in optimizer.slot_shapes(dim)},
        init_rng=P(), insert_failures=P())


def state_specs(optimizer: SparseOptimizer, dim: int, spec: HashShardingSpec):
    table = table_state_specs(optimizer, dim, spec)
    if spec.is_cached:
        return hot_cache.CachedState(
            table=table,
            cache=hot_cache.HotCacheState(
                keys=P(), rows=P(),
                slots={name: P() for name in table.slots}))
    return table


def create_sharded_hash_table(meta: EmbeddingVariableMeta,
                              optimizer: Any,
                              *,
                              mesh: Mesh,
                              spec: HashShardingSpec,
                              rng: Optional[jax.Array] = None,
                              key_dtype=jnp.int32,
                              wrap_cache: bool = True):
    """Allocate per-shard empty hash tables across the mesh.

    The per-key deterministic init uses the shared base rng (not folded per
    shard): a key has exactly one owner, and keeping the base rng global makes
    row init independent of shard count (checkpoints stay comparable when
    resharded).
    """
    optimizer = make_optimizer(optimizer)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    dim = meta.embedding_dim

    def _init(key):
        return hash_lib.create_hash_table(
            meta, optimizer,
            capacity=spec.capacity_per_shard, rng=key, key_dtype=key_dtype,
            key_width=spec.key_width)

    fn = shard_map(_init, mesh=mesh,
                   in_specs=(P(),),
                   out_specs=table_state_specs(optimizer, dim, spec),
                   check_vma=False)
    state = jax.jit(fn)(rng)
    if wrap_cache:
        # all-pad replica: zero hits (pure-a2a behavior) until the first
        # admission refresh (hot_cache.HotCacheManager / build_cache).
        # ``wrap_cache=False`` returns the bare table (callers composing
        # their own jitted init wrap eagerly afterwards).
        return hot_cache.attach_empty(state, spec, mesh)
    return state


def _mask_non_owned(spec: HashShardingSpec, flat: jnp.ndarray,
                    me: jnp.ndarray) -> jnp.ndarray:
    empty = hash_lib.empty_key(flat.dtype)

    @scope.stage("route")
    def mask(flat, me):
        if hash_lib.is_wide(flat):
            owned = (spec.owner_shard(flat) == me) & (flat[:, 1] != empty)
            return jnp.where(owned[:, None], flat, empty)
        owned = (spec.owner_shard(flat) == me) & (flat != empty)
        return jnp.where(owned, flat, empty)

    return mask(flat, me)


def _my_shard(mesh: Mesh, spec: HashShardingSpec) -> jnp.ndarray:
    axes = spec.shard_axes
    return a2a.linear_shard_id(axes, tuple(mesh.shape[a] for a in axes))


OFFLOAD_INSERT_STAGE = "offload_insert"     # the packed insert in a trace


@functools.lru_cache(maxsize=None)
def _insert_rows_program(mesh: Mesh, spec: HashShardingSpec,
                         slot_names: tuple, in_slot_names: tuple,
                         record_stats: bool = False, donate: bool = True):
    """Cached jitted insert program: the checkpoint loader streams many
    same-shaped chunks, and rebuilding the shard_map closure per chunk would
    retrace every call.

    The table operands (keys, weights, slots: arguments 0-2) are donated,
    so the outputs alias them and the table is updated in place, as the
    jitted step does with ``TrainState.emb``: without it every call
    returns a second copy of the table. The running count of failed
    inserts goes in and comes out with this call's added, so no second
    program follows the insert."""

    def _insert(tkeys, tweights, tslots, failures, init_rng, k, w, srows):
        local = hash_lib.HashTableState(
            keys=tkeys, weights=tweights, slots=tslots, init_rng=init_rng,
            insert_failures=jnp.zeros((), jnp.int32))
        flat = k.reshape(-1, 2) if spec.wide else k.ravel()
        masked = _mask_non_owned(spec, flat, _my_shard(mesh, spec))
        new = hash_lib.insert_rows(local, masked, w, srows or None,
                                   max_probes=spec.max_probes,
                                   record_stats=record_stats)
        failed = lax.psum(new.insert_failures, spec.shard_axes)
        return new.keys, new.weights, new.slots, failures + failed

    row = spec.row_spec()
    slot_specs = {name: row for name in slot_names}
    in_slot_specs = {name: P() for name in in_slot_names}
    fn = shard_map(_insert, mesh=mesh,
                   in_specs=(row, row, slot_specs, P(), P(), P(), P(),
                             in_slot_specs),
                   out_specs=(row, row, slot_specs, P()),
                   check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1, 2) if donate else ())


def insert_rows_sharded(state: hash_lib.HashTableState,
                        keys: jnp.ndarray,
                        weights: jnp.ndarray,
                        slot_rows=None,
                        *,
                        mesh: Mesh,
                        spec: HashShardingSpec,
                        donate: bool = True) -> hash_lib.HashTableState:
    """Load-path row delivery: every shard inserts its owned keys verbatim.

    ``keys``/``weights``/``slot_rows`` are replicated host batches (the
    checkpoint loader streams chunks); non-owned keys are masked to EMPTY and
    skipped locally — the reference's owning-server delivery
    (EmbeddingLoadOperator.cpp:58-111).

    The table is updated IN PLACE: ``state``'s keys, weights and slots are
    donated to the program and must not be read again (use the returned
    state). ``donate=False`` keeps them alive and returns a copy, as
    ``sharded_table.deliver_rows_sharded`` does for the serving hot-swap,
    whose in-flight readers hold the pre-swap state.
    """
    slot_rows = slot_rows or {}
    fn = _insert_rows_program(mesh, spec, tuple(state.slots),
                              tuple(slot_rows),
                              observability.evaluate_performance(), donate)
    tkeys, tweights, tslots, failures = fn(
        state.keys, state.weights, state.slots, state.insert_failures,
        state.init_rng, keys, weights, slot_rows)
    return hash_lib.HashTableState(
        keys=tkeys, weights=tweights, slots=tslots,
        init_rng=state.init_rng, insert_failures=failures)


@functools.lru_cache(maxsize=None)
def _insert_packed_program(mesh: Mesh, spec: HashShardingSpec,
                           dim: int, layout: tuple,
                           record_stats: bool = False):
    """Jitted insert taking ONE packed f32 buffer instead of the
    keys/weights/slots pytree: column 0 carries int32 keys bitcast to
    f32, columns [1, 1+dim) the weight row, the rest each slot's row
    (``layout`` = ((name, start_col, n_cols, row_shape), ...), static).

    Rationale: the offload tier ships an insert payload to the device
    EVERY step; one coalesced transfer replaces 2+len(slots) separate
    host->device arrays, and the per-transfer fixed cost is the one that
    shows (`python -m tools.offload_diag puts`). The unpack (slice + bitcast) fuses into
    the insert program, which updates the table in place as
    :func:`_insert_rows_program` does. Only the offload tier packs its
    rows, so the program is named for it: ``OFFLOAD_INSERT_STAGE`` is the
    module's name in a device trace and, through ``scope.stage``, a part
    of every instruction's ``op_name``."""

    def _insert(tkeys, tweights, tslots, failures, init_rng, packed):
        local = hash_lib.HashTableState(
            keys=tkeys, weights=tweights, slots=tslots, init_rng=init_rng,
            insert_failures=jnp.zeros((), jnp.int32))
        n = packed.shape[0]
        k = lax.bitcast_convert_type(packed[:, 0], jnp.int32)
        w = packed[:, 1:1 + dim]
        srows = {name: packed[:, s:s + c].reshape((n,) + shape)
                 for name, s, c, shape in layout}
        masked = _mask_non_owned(spec, k, _my_shard(mesh, spec))
        new = hash_lib.insert_rows(local, masked, w, srows or None,
                                   max_probes=spec.max_probes,
                                   record_stats=record_stats)
        failed = lax.psum(new.insert_failures, spec.shard_axes)
        return new.keys, new.weights, new.slots, failures + failed

    row = spec.row_spec()
    slot_specs = {name: row for name, _, _, _ in layout}
    fn = shard_map(_insert, mesh=mesh,
                   in_specs=(row, row, slot_specs, P(), P(), P()),
                   out_specs=(row, row, slot_specs, P()),
                   check_vma=False)

    def named(*args):
        return scope.stage(OFFLOAD_INSERT_STAGE)(fn)(*args)
    named.__name__ = named.__qualname__ = OFFLOAD_INSERT_STAGE
    return jax.jit(named, donate_argnums=(0, 1, 2))


def insert_rows_sharded_packed(state: hash_lib.HashTableState,
                               packed: jnp.ndarray,
                               layout: tuple,
                               *,
                               mesh: Mesh,
                               spec: HashShardingSpec
                               ) -> hash_lib.HashTableState:
    """:func:`insert_rows_sharded` behavior from ONE packed f32 buffer
    (int32 keys only — the offload cache's key plane; wide tables use
    the unpacked path), in place like it. See
    :func:`_insert_packed_program`."""
    if spec.wide:
        raise ValueError("packed insert supports int32-key tables only")
    dim = state.weights.shape[-1]
    fn = _insert_packed_program(mesh, spec, dim, layout,
                                observability.evaluate_performance())
    tkeys, tweights, tslots, failures = fn(
        state.keys, state.weights, state.slots, state.insert_failures,
        state.init_rng, packed)
    return hash_lib.HashTableState(
        keys=tkeys, weights=tweights, slots=tslots,
        init_rng=state.init_rng, insert_failures=failures)


@functools.lru_cache(maxsize=None)
def _read_rows_program(mesh: Mesh, spec: HashShardingSpec,
                       slot_names: tuple):
    """Jitted read of whole rows (weights AND optimizer slots) for given
    keys: each shard finds the keys it owns and gathers their rows, nought
    elsewhere, and a psum over the shard axes hands every device the
    rows. Nothing is inserted or drawn: an absent key reads ``found``
    false."""

    def _read(tkeys, tweights, tslots, k):
        flat = k.reshape(-1, 2) if spec.wide else k.ravel()
        masked = _mask_non_owned(spec, flat, _my_shard(mesh, spec))
        slot = hash_lib.find_rows(tkeys, masked, max_probes=spec.max_probes)
        found = slot >= 0
        at = jnp.where(found, slot, 0)

        def take(rows):
            got = jnp.take(rows, at, axis=0)
            mask = found.reshape((-1,) + (1,) * (got.ndim - 1))
            return lax.psum(jnp.where(mask, got, jnp.zeros((), got.dtype)),
                            spec.shard_axes)

        return (lax.psum(found.astype(jnp.int32), spec.shard_axes) > 0,
                take(tweights), {name: take(tslots[name])
                                 for name in slot_names})

    row = spec.row_spec()
    slot_specs = {name: row for name in slot_names}
    fn = shard_map(_read, mesh=mesh,
                   in_specs=(row, row, slot_specs, P()),
                   out_specs=(P(), P(), {name: P() for name in slot_names}),
                   check_vma=False)
    return jax.jit(fn)


def read_rows_sharded(state: hash_lib.HashTableState, keys: jnp.ndarray, *,
                      mesh: Mesh, spec: HashShardingSpec):
    """(found [n], weights [n, dim], {slot: [n, ...]}) of ``keys``
    (replicated; EMPTY keys read not found), as the table holds them now:
    the write-back path's read, which copies the rows it names and not
    the table."""
    return _read_rows_program(mesh, spec, tuple(state.slots))(
        state.keys, state.weights, state.slots, keys)


@functools.lru_cache(maxsize=None)
def _pull_program(mesh: Mesh, spec: HashShardingSpec, initializer: Any,
                  dim: int, batch_sharded: bool,
                  record_stats: bool = False):
    batch_spec = P(spec.data_axis) if batch_sharded else P()

    # a grouped-plane table addressed PER TABLE takes the plain a2a
    # program — grouping only exists at the collection level
    if (spec.plane != "psum" and spec.num_shards > 1) \
            or spec.is_cached:
        grid_axes, grid_sizes, split_axes, split_sizes = a2a.grid_info(
            mesh, spec.shard_axes, spec.model_axis, batch_sharded)

        def _pull_core(keys, weights, init_rng, flat):
            me = a2a.linear_shard_id(grid_axes, grid_sizes)
            local = hash_lib.HashTableState(
                keys=keys, weights=weights, slots={}, init_rng=init_rng,
                insert_failures=jnp.zeros((), jnp.int32))
            sentinel = hash_lib.empty_key(flat.dtype)

            def resolve(q):
                masked = _mask_non_owned(spec, q, me)
                return hash_lib.pull(local, masked, initializer,
                                     max_probes=spec.max_probes)

            def owner(q):
                valid = (q[:, 1] if spec.wide else q) != sentinel
                return jnp.where(valid, spec.owner_shard(q),
                                 spec.num_shards).astype(jnp.int32)

            return a2a.exchange_pull(
                flat, resolve, owner, sentinel=sentinel, dim=dim,
                num_shards=spec.num_shards, grid_axes=grid_axes,
                grid_sizes=grid_sizes, split_axes=split_axes,
                split_sizes=split_sizes, capacity=spec.a2a_capacity,
                slack=spec.a2a_slack, record_stats=record_stats,
                wire_dtype=spec.pull_wire_dtype)

        if spec.is_cached:
            def _pull(keys, weights, init_rng, ckeys, crows, idx):
                flat = idx.reshape(-1, 2) if spec.wide else idx.ravel()
                out_shape = (idx.shape[:-1] if spec.wide else idx.shape) \
                    + (dim,)
                sentinel = hash_lib.empty_key(flat.dtype)
                valid = (flat[:, 1] if spec.wide else flat) != sentinel
                pos, hit = hot_cache.lookup(ckeys, flat, valid)
                served = jnp.where(hit[:, None],
                                   jnp.take(crows, pos, axis=0),
                                   jnp.zeros((1, dim), crows.dtype))
                hot_cache.record_cache_stats(
                    hit, valid,
                    entry_bytes=dim * crows.dtype.itemsize
                    + (8 if spec.wide else 4),
                    split_axes=split_axes, split_sizes=split_sizes,
                    record=record_stats)
                resid = hot_cache.mask_hits(flat, hit, sentinel)
                rows = _pull_core(keys, weights, init_rng, resid)
                return (rows + served).reshape(out_shape)
        else:
            def _pull(keys, weights, init_rng, idx):
                flat = idx.reshape(-1, 2) if spec.wide else idx.ravel()
                out_shape = (idx.shape[:-1] if spec.wide else idx.shape) \
                    + (dim,)
                return _pull_core(keys, weights, init_rng,
                                  flat).reshape(out_shape)
    else:
        def _pull(keys, weights, init_rng, idx):
            local = hash_lib.HashTableState(
                keys=keys, weights=weights, slots={}, init_rng=init_rng,
                insert_failures=jnp.zeros((), jnp.int32))
            flat = idx.reshape(-1, 2) if spec.wide else idx.ravel()
            out_shape = (idx.shape[:-1] if spec.wide else idx.shape) \
                + (dim,)
            flat = _mask_non_owned(spec, flat,
                                   lax.axis_index(spec.model_axis))
            rows = hash_lib.pull(local, flat, initializer,
                                 max_probes=spec.max_probes)
            rows = scope.stage("exchange")(
                lambda rows: lax.psum(rows, spec.model_axis))(rows)
            return rows.reshape(out_shape)

    row = spec.row_spec()
    if spec.is_cached:
        in_specs = (row, row, P(), P(), P(), batch_spec)
    else:
        in_specs = (row, row, P(), batch_spec)
    # plane-identifiable HLO module name for the contract audits
    # (analysis/contracts.py): failures name the plane that regressed
    _pull.__name__ = f"hash_pull_{spec.plane_label.replace('+', '_')}"
    fn = shard_map(_pull, mesh=mesh,
                   in_specs=in_specs,
                   out_specs=batch_spec,
                   check_vma=False)
    return jax.jit(fn)


def pull_sharded(state,
                 indices: jnp.ndarray,
                 initializer: Any,
                 *,
                 mesh: Mesh,
                 spec: HashShardingSpec,
                 batch_sharded: bool = True) -> jnp.ndarray:
    """Distributed hash lookup: the owner shard resolves each key.

    Missing-but-valid keys get their deterministic init row (computed only by
    the owner shard); EMPTY-sentinel keys return zero rows. ``initializer=
    None`` = read-only serving contract (missing keys -> zeros). On the
    ``"a2a+cache"`` plane ``state`` is a :class:`hot_cache.CachedState`;
    hot keys are served from the local replica (cached keys are always
    PRESENT in the table — admission rejects absent ones — so the replica
    can never shadow the deterministic-init contract).
    """
    record = observability.evaluate_performance()
    if initializer is not None:
        initializer = make_initializer(initializer)
    if spec.is_cached:
        table = state.table
        dim = table.weights.shape[-1]
        fn = _pull_program(mesh, spec, initializer, dim, batch_sharded,
                           record)
        return observability.plane_timed(
            "pull", spec.plane_label, record, fn, table.keys,
            table.weights, table.init_rng, state.cache.keys,
            state.cache.rows, indices)
    state = precision.unwrap(state)
    dim = state.weights.shape[-1]
    fn = _pull_program(mesh, spec, initializer, dim, batch_sharded, record)
    return observability.plane_timed(
        "pull", spec.plane_label, record, fn, state.keys, state.weights,
        state.init_rng, indices)


@functools.lru_cache(maxsize=None)
def _apply_program(mesh: Mesh, spec: HashShardingSpec,
                   optimizer: SparseOptimizer, initializer: Any, dim: int,
                   batch_sharded: bool, dedup_capacity: Optional[int],
                   slot_names: tuple, record_stats: bool = False):
    batch_spec = P(spec.data_axis) if batch_sharded else P()

    if (spec.plane != "psum" and spec.num_shards > 1) \
            or spec.is_cached:
        grid_axes, grid_sizes, split_axes, split_sizes = a2a.grid_info(
            mesh, spec.shard_axes, spec.model_axis, batch_sharded)

        def _push_core(keys, weights, slots, init_rng, flat, g2, ef=None):
            me = a2a.linear_shard_id(grid_axes, grid_sizes)
            sentinel = hash_lib.empty_key(
                flat.dtype if not spec.wide else jnp.int32)

            def owner(q):
                valid = (q[:, 1] if spec.wide else q) != sentinel
                return jnp.where(valid, spec.owner_shard(q),
                                 spec.num_shards).astype(jnp.int32)

            def merge_fn(st, q, grads, counts):
                tkeys, fails = st
                cur = hash_lib.HashTableState(
                    keys=tkeys, weights=weights, slots=slots,
                    init_rng=init_rng,
                    insert_failures=jnp.zeros((), jnp.int32))
                tkeys, failed, merged = hash_lib.merge_gradients(
                    cur, initializer, _mask_non_owned(spec, q, me), grads,
                    dedup_capacity=dedup_capacity,
                    max_probes=spec.max_probes, in_counts=counts,
                    record_stats=record_stats)
                return (tkeys, fails + failed), merged

            out = a2a.exchange_push(
                flat, g2, (keys, jnp.zeros((), jnp.int32)), merge_fn, owner,
                sentinel=sentinel, num_shards=spec.num_shards,
                grid_axes=grid_axes, grid_sizes=grid_sizes,
                split_axes=split_axes, split_sizes=split_sizes,
                capacity=spec.a2a_capacity, slack=spec.a2a_slack,
                record_stats=record_stats,
                wire_dtype=spec.push_wire_dtype, ef_state=ef)
            ((keys, fails), merged), new_ef = \
                out if ef is not None else (out, None)
            weights, slots = table_lib.apply_rows(
                weights, slots, optimizer, *merged,
                record_stats=record_stats)
            table = (keys, weights, slots, fails)
            return table if ef is None else (table, new_ef)

        if spec.is_cached:
            def _apply(keys, weights, slots, init_rng, ckeys, crows,
                       cslots, idx, g):
                me = a2a.linear_shard_id(grid_axes, grid_sizes)
                flat = idx.reshape(-1, 2) if spec.wide else idx.ravel()
                g2 = g.reshape(-1, dim)
                sentinel = hash_lib.empty_key(flat.dtype)
                valid = (flat[:, 1] if spec.wide else flat) != sentinel
                pos, hit = hot_cache.lookup(ckeys, flat, valid)
                k = ckeys.shape[0]
                summed, counts = hot_cache.cache_pre_reduce(
                    pos, hit, g2, k, split_axes, split_sizes, grid_axes)
                hot_cache.record_cache_stats(
                    hit, valid,
                    entry_bytes=dim * crows.dtype.itemsize
                    + (12 if spec.wide else 8),
                    split_axes=split_axes, split_sizes=split_sizes,
                    record=record_stats)
                resid = hot_cache.mask_hits(flat, hit, sentinel)
                tkeys, tweights, tslots, fails = _push_core(
                    keys, weights, slots, init_rng, resid, g2)
                # identical psum'd totals on every device -> identical
                # replica update everywhere; the owner scatters its rows
                # back so the table stays authoritative
                cache = hot_cache.HotCacheState(keys=ckeys, rows=crows,
                                                slots=cslots)
                cache = hot_cache.update_replica(optimizer, cache, summed,
                                                 counts)
                # owner write-back: admitted keys are PRESENT, so the
                # probe hits; the scatter drops non-owned / untouched rows
                mine_keys = _mask_non_owned(spec, ckeys, me)
                slot = hash_lib.find_rows(tkeys, mine_keys,
                                          spec.max_probes)
                touched = (slot >= 0) & (counts > 0)
                oob = jnp.asarray(tweights.shape[0], jnp.int32)
                sc = jnp.where(touched, slot, oob)
                tweights = tweights.at[sc].set(
                    cache.rows.astype(tweights.dtype), mode="drop")
                tslots = {name: tslots[name].at[sc].set(
                    cache.slots[name].astype(tslots[name].dtype),
                    mode="drop") for name in tslots}
                return (tkeys, tweights, tslots, cache.rows, cache.slots,
                        lax.psum(fails, spec.shard_axes))
        elif spec.is_int8_ef:
            def _apply(keys, weights, slots, init_rng, ef_keys, ef_resid,
                       idx, g):
                flat = idx.reshape(-1, 2) if spec.wide else idx.ravel()
                res, (nek, ner) = _push_core(
                    keys, weights, slots, init_rng, flat,
                    g.reshape(-1, dim), ef=(ef_keys, ef_resid))
                tkeys, tweights, tslots, fails = res
                return (tkeys, tweights, tslots,
                        lax.psum(fails, spec.shard_axes), nek, ner)
        else:
            def _apply(keys, weights, slots, init_rng, idx, g):
                flat = idx.reshape(-1, 2) if spec.wide else idx.ravel()
                tkeys, tweights, tslots, fails = _push_core(
                    keys, weights, slots, init_rng, flat,
                    g.reshape(-1, dim))
                return (tkeys, tweights, tslots,
                        lax.psum(fails, spec.shard_axes))
    else:
        def _apply(keys, weights, slots, init_rng, idx, g):
            flat = idx.reshape(-1, 2) if spec.wide else idx.ravel()
            g2 = g.reshape(-1, dim)
            if batch_sharded:
                flat, g2 = scope.stage("exchange")(
                    lambda *xs: tuple(lax.all_gather(x, spec.data_axis,
                                                     tiled=True)
                                      for x in xs))(flat, g2)
            flat = _mask_non_owned(spec, flat,
                                   lax.axis_index(spec.model_axis))
            local = hash_lib.HashTableState(
                keys=keys, weights=weights, slots=slots, init_rng=init_rng,
                insert_failures=jnp.zeros((), jnp.int32))
            new = hash_lib.apply_gradients(
                local, optimizer, initializer, flat, g2,
                dedup_capacity=dedup_capacity, max_probes=spec.max_probes,
                record_stats=record_stats)
            # per-shard failure deltas -> replicated global total
            failed = lax.psum(new.insert_failures, spec.model_axis)
            return new.keys, new.weights, new.slots, failed

    row = spec.row_spec()
    slot_specs = {name: row for name in slot_names}
    _apply.__name__ = f"hash_push_{spec.plane_label.replace('+', '_')}"
    if spec.is_cached:
        cache_slot_specs = {name: P() for name in slot_names}
        fn = shard_map(_apply, mesh=mesh,
                       in_specs=(row, row, slot_specs, P(), P(), P(),
                                 cache_slot_specs, batch_spec, batch_spec),
                       out_specs=(row, row, slot_specs, P(),
                                  cache_slot_specs, P()),
                       check_vma=False)
    elif spec.is_int8_ef and spec.num_shards > 1:
        ef_spec = P(spec.shard_axes)
        fn = shard_map(_apply, mesh=mesh,
                       in_specs=(row, row, slot_specs, P(), ef_spec,
                                 ef_spec, batch_spec, batch_spec),
                       out_specs=(row, row, slot_specs, P(), ef_spec,
                                  ef_spec),
                       check_vma=False)
    else:
        fn = shard_map(_apply, mesh=mesh,
                       in_specs=(row, row, slot_specs, P(),
                                 batch_spec, batch_spec),
                       out_specs=(row, row, slot_specs, P()),
                       check_vma=False)
    return jax.jit(fn)


def apply_gradients_sharded(state,
                            optimizer: SparseOptimizer,
                            initializer: Any,
                            indices: jnp.ndarray,
                            grads: jnp.ndarray,
                            *,
                            mesh: Mesh,
                            spec: HashShardingSpec,
                            batch_sharded: bool = True,
                            dedup_capacity: Optional[int] = None):
    """Distributed push+update: each key's grads reach its single owner
    shard. On the ``"a2a+cache"`` plane ``state`` is a
    :class:`hot_cache.CachedState`: hot keys pre-reduce locally + one psum
    over the K replica rows, and the owner writes the updated rows back."""
    optimizer = make_optimizer(optimizer)
    initializer = make_initializer(initializer) if initializer is not None \
        else None
    record = observability.evaluate_performance()
    if spec.is_cached:
        table = state.table
        dim = table.weights.shape[-1]
        fn = _apply_program(mesh, spec, optimizer, initializer, dim,
                            batch_sharded, dedup_capacity,
                            tuple(table.slots), record)
        keys, weights, slots, crows, cslots, failed = \
            observability.plane_timed(
                "push", spec.plane_label, record, fn,
                table.keys, table.weights, table.slots, table.init_rng,
                state.cache.keys, state.cache.rows, state.cache.slots,
                indices, grads)
        new_table = hash_lib.HashTableState(
            keys=keys, weights=weights, slots=slots,
            init_rng=table.init_rng,
            insert_failures=table.insert_failures + failed)
        return hot_cache.CachedState(
            table=new_table,
            cache=hot_cache.HotCacheState(keys=state.cache.keys,
                                          rows=crows, slots=cslots))
    if spec.is_int8_ef and spec.num_shards > 1:
        bare = precision.unwrap(state)
        dim = bare.weights.shape[-1]
        sentinel, key_dtype = precision.ef_key_space(
            use_hash=True, wide=spec.wide, key_dtype=bare.keys.dtype)
        n_flat = int(np.prod(indices.shape))
        if spec.wide:
            n_flat //= 2
        table, ef_keys, ef_resid = precision.ensure_ef(
            state, dim=dim, wide=spec.wide, sentinel=sentinel,
            n_flat=n_flat, data=mesh.shape[spec.data_axis],
            model=mesh.shape[spec.model_axis],
            batch_sharded=batch_sharded, key_dtype=key_dtype)
        fn = _apply_program(mesh, spec, optimizer, initializer, dim,
                            batch_sharded, dedup_capacity,
                            tuple(table.slots), record)
        keys, weights, slots, failed, nek, ner = \
            observability.plane_timed(
                "push", spec.plane_label, record, fn,
                table.keys, table.weights, table.slots, table.init_rng,
                ef_keys, ef_resid, indices, grads)
        new_table = hash_lib.HashTableState(
            keys=keys, weights=weights, slots=slots,
            init_rng=table.init_rng,
            insert_failures=table.insert_failures + failed)
        return precision.EFState(table=new_table, keys=nek, resid=ner)
    state = precision.unwrap(state)
    dim = state.weights.shape[-1]
    fn = _apply_program(mesh, spec, optimizer, initializer, dim,
                        batch_sharded, dedup_capacity, tuple(state.slots),
                        record)
    keys, weights, slots, failed = observability.plane_timed(
        "push", spec.plane_label, record, fn,
        state.keys, state.weights, state.slots, state.init_rng,
        indices, grads)
    return hash_lib.HashTableState(
        keys=keys, weights=weights, slots=slots,
        init_rng=state.init_rng,
        insert_failures=state.insert_failures + failed)

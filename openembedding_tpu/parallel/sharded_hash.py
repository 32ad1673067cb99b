"""Hash-table embeddings sharded over the device mesh: layout, creation, the
bulk insert and read of whole rows, and the hash store of the shared
pull/push builder (``parallel/sharded.py``, which describes the planes).

Same data planes as ``sharded_table`` but for unbounded key spaces: each
shard is one open-addressing table, and keys are partitioned ``key %
num_shards`` (the reference's modulo shard layout,
/root/reference/openembedding/server/EmbeddingPullOperator.cpp:73-78,
applied to hashed keys, which are uniform by construction) to their single
owner. Non-owned keys are masked to the EMPTY sentinel before the local
table call (zero pull rows / dropped updates), so the masked-local body's
psum over the model axis reconstructs the full batch exactly once.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..analysis import scope
from ..meta import EmbeddingVariableMeta
from ..ops import dedup
from ..utils import observability
from ..optim.initializers import make_initializer
from ..optim.optimizers import SparseOptimizer, make_optimizer
from .. import hash_table as hash_lib
from .. import table as table_lib
from . import alltoall as a2a
from . import precision
from . import sharded
from . import sharded_table as st


@dataclasses.dataclass(frozen=True)
class HashShardingSpec(sharded.PlaneSpec):
    """Static layout of one hash table over the mesh."""

    capacity_per_shard: int
    max_probes: int = hash_lib.DEFAULT_MAX_PROBES
    key_width: int = 32  # 64 = [n, 2] int32 (lo, hi) pairs, x64-off

    @property
    def wide(self) -> bool:
        return self.key_width == 64

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
                            plane: str = "a2a", a2a_capacity: int = 0,
                            a2a_slack: float = 2.0, key_width: int = 32,
                            cache_k: int = 0,
                            exchange_precision: str = "f32",
                            push_precision: str = "f32") -> HashShardingSpec:
    """num_shards=-1 => one shard per device ("a2a") / per model slice ("psum").

    ``plane="a2a+cache"``: a2a layout plus a ``cache_k``-row hot-row replica
    on every device (``parallel/hot_cache.py``); 0 picks the default size.
    A ``+bf16``/``+int8`` plane suffix selects the compressed-exchange
    rungs (``parallel/precision.py``).
    """
    if key_width not in (32, 64):
        raise ValueError(f"key_width must be 32 or 64, got {key_width}")
    plane, num_shards, cache_k, exchange_precision, push_precision = \
        st._resolve_plane(mesh, plane, num_shards, cache_k,
                          exchange_precision, push_precision)
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
    return st.with_cache_specs(table_state_specs(optimizer, dim, spec), spec)


def create_sharded_hash_table(meta: EmbeddingVariableMeta, optimizer: Any, *,
                              mesh: Mesh, spec: HashShardingSpec,
                              rng: Optional[jax.Array] = None,
                              key_dtype=jnp.int32, wrap_cache: bool = True):
    """Allocate per-shard empty hash tables across the mesh.

    The per-key deterministic init uses the shared base rng (not folded per
    shard): a key has exactly one owner, and keeping the base rng global makes
    row init independent of shard count (checkpoints stay comparable when
    resharded).
    """
    optimizer = make_optimizer(optimizer)
    dim = meta.embedding_dim

    def _init(key):
        return hash_lib.create_hash_table(
            meta, optimizer,
            capacity=spec.capacity_per_shard, rng=key, key_dtype=key_dtype,
            key_width=spec.key_width)

    return st._create(_init, table_state_specs(optimizer, dim, spec),
                      mesh=mesh, spec=spec, rng=rng, wrap_cache=wrap_cache)


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
        local = HashStore(spec).local(tkeys, tweights, tslots, init_rng)
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
    return state.replace(keys=tkeys, weights=tweights, slots=tslots,
                         insert_failures=failures)


@functools.lru_cache(maxsize=None)
def _insert_packed_program(mesh: Mesh, spec: HashShardingSpec,
                           dim: int, layout: tuple,
                           record_stats: bool = False):
    """Jitted insert taking ONE packed f32 buffer instead of the
    keys/weights/slots pytree: column 0 carries int32 keys bitcast to
    f32, columns [1, 1+dim) the weight row, the rest each slot's row
    (``layout`` = ((name, start_col, n_cols, row_shape), ...), static).
    A wide table's buffer is int32: columns 0 and 1 are a key's (lo, hi)
    words as they are, the f32 rows ride as their bits from column 2 on
    (a key word is any 32 bits, a NaN's among them: no float leaves the
    unpack but by a bitcast).

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
        local = HashStore(spec).local(tkeys, tweights, tslots, init_rng)
        n = packed.shape[0]
        if spec.wide:
            k, first = packed[:, :2], 2
            packed = lax.bitcast_convert_type(packed, jnp.float32)
        else:
            k, first = lax.bitcast_convert_type(packed[:, 0], jnp.int32), 1
        w = packed[:, first:first + dim]
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
    """:func:`insert_rows_sharded` behavior from ONE packed buffer, in
    place like it: f32 with the int32 key in column 0 (a bounded offload
    cache's key plane), or for a wide table int32 with the key's two
    words in columns 0-1 and the f32 rows as their bits. See
    :func:`_insert_packed_program`."""
    want = jnp.dtype(jnp.int32 if spec.wide else jnp.float32)
    if packed.dtype != want:
        raise ValueError(f"packed insert of a {spec.key_width}-bit-key "
                         f"table takes a {want.name} buffer, got "
                         f"{packed.dtype}")
    dim = state.weights.shape[-1]
    fn = _insert_packed_program(mesh, spec, dim, layout,
                                observability.evaluate_performance())
    tkeys, tweights, tslots, failures = fn(
        state.keys, state.weights, state.slots, state.insert_failures,
        state.init_rng, packed)
    return state.replace(keys=tkeys, weights=tweights, slots=tslots,
                         insert_failures=failures)


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
def _snapshot_keys_program(mesh: Mesh, spec: HashShardingSpec, arrays: int):
    """Cached find-and-gather program of :func:`snapshot_keys_sharded`;
    one compile a staging length."""

    def ckpt_gather_keys(tkeys, arrays, k, count):
        flat = k.reshape(-1, 2) if spec.wide else k.ravel()
        if spec.num_shards > 1:     # a key another shard owns: not found,
            flat = _mask_non_owned(spec, flat, _my_shard(mesh, spec))
        found, staged = hash_lib.snapshot_keys(
            tkeys, arrays, flat, count, max_probes=spec.max_probes)
        if spec.num_shards > 1:     # a zero row here; the owner's in the sum
            found = lax.psum(found.astype(jnp.int32), spec.shard_axes) > 0
            staged = [lax.psum(s, spec.shard_axes) for s in staged]
        return found, staged

    row = spec.row_spec()
    fn = shard_map(ckpt_gather_keys, mesh=mesh,
                   in_specs=(row, [row] * arrays, P(), P()),
                   out_specs=(P(), [P()] * arrays), check_vma=False)
    return jax.jit(fn)


def snapshot_keys_sharded(table_keys, arrays, keys: jnp.ndarray, count, *,
                          mesh: Mesh, spec: HashShardingSpec):
    """Find the keys ``keys[:count]`` (replicated, the table's key form,
    EMPTY past ``count``) and gather their rows of every sharded array of
    ``arrays`` into replicated staging buffers of ``keys``'s length: a
    delta checkpoint's snapshot of a hash table
    (``hash_table.snapshot_keys``), the twin of
    ``sharded_table.snapshot_rows_sharded``. ``(found [n], rows)``.
    Nothing is donated, inserted or waited for: the program runs behind
    whatever was dispatched before it."""
    return _snapshot_keys_program(mesh, spec, len(arrays))(
        table_keys, list(arrays), keys, jnp.asarray(count, jnp.int32))


@dataclasses.dataclass(frozen=True)
class HashStore:
    """A hash table behind ``parallel/sharded.py``'s builder (which lists
    what a store answers): a key's slot is found by probing, fresh keys are
    inserted behind the merge (``apply_merged``), so the carry out of a
    push is the key array and the count of keys no probe window held. A
    missing-but-valid key pulls its
    deterministic init row (computed only by the owner shard), an EMPTY one
    zeros; ``initializer=None`` is the read-only serving contract (missing
    keys -> zeros). Cached keys (``"a2a+cache"``) are always PRESENT in the
    table — admission rejects absent ones — so the replica can never shadow
    the deterministic-init contract."""

    spec: HashShardingSpec
    initializer: Any = None
    prefix = "hash_"

    @property
    def key_bytes(self) -> int:
        return 8 if self.spec.wide else 4

    def operands(self, table):
        return table.keys, table.weights, table.slots, table.init_rng

    def specs(self, slot_names: tuple):
        # out of a push the step's failure count sits where init_rng went in
        row = self.spec.row_spec()
        return row, row, {name: row for name in slot_names}, P()

    def local(self, keys, weights, slots, init_rng):
        return hash_lib.HashTableState(
            keys=keys, weights=weights, slots=slots, init_rng=init_rng,
            insert_failures=jnp.zeros((), jnp.int32))

    def rebuild(self, table, outs):
        keys, weights, slots, failed = outs
        return table.replace(keys=keys, weights=weights, slots=slots,
                             insert_failures=table.insert_failures + failed)

    def batch_shape(self, shape: tuple) -> tuple:
        return shape[:-1] if self.spec.wide else shape

    def sentinel(self, dtype):
        return hash_lib.empty_key(jnp.int32 if self.spec.wide else dtype)

    def valid(self, flat):
        return (flat[:, 1] if self.spec.wide else flat) \
            != self.sentinel(flat.dtype)

    def owner(self, keys):
        return jnp.where(self.valid(keys), self.spec.owner_shard(keys),
                         self.spec.num_shards).astype(jnp.int32)

    def slot_of(self, carry, keys, me):
        return hash_lib.find_rows(carry[0],
                                  _mask_non_owned(self.spec, keys, me),
                                  self.spec.max_probes)

    def resolve(self, local, keys, me):
        return hash_lib.pull(local, _mask_non_owned(self.spec, keys, me),
                             self.initializer,
                             max_probes=self.spec.max_probes)

    def read_local(self, local, flat):
        return self.resolve(local, flat,
                            lax.axis_index(self.spec.model_axis))

    def own(self, plan, me):
        """``plan`` as shard ``me`` sees it: a key another shard owns is
        EMPTY and not valid."""
        keys = _mask_non_owned(self.spec, plan.uniq, me)
        return plan.replace(uniq=keys, valid=plan.valid & self.valid(keys))

    def routing(self) -> tuple:
        """What :meth:`owner` reads of the spec: two stores that agree on
        it send one column's keys to the same owners."""
        return self.spec.num_shards, self.spec.key_width

    def read_plan(self, local, plan, record_stats, me=None):
        mine = self.own(plan, lax.axis_index(self.spec.model_axis)
                        if me is None else me)
        return hash_lib.pull_distinct(
            local, mine.uniq, mine.valid, self.initializer,
            self.spec.max_probes, positions=plan.inverse.shape[0],
            record_stats=record_stats)

    def merge(self, local, keys, grads, counts, me, *, dedup_capacity,
              plan=None, resolved=None, carries=False):
        """``(uniq, valid, summed, counts)`` of the owner's distinct keys,
        and where ``carries`` ``(rows, found, known)`` behind them: each
        slot's weight row and its key's slot as the step's pull resolved
        them, ``known`` throughout; without a resolution (the gathered
        branch) a key's init row, no slot and ``known`` nowhere, for
        ``apply_merged`` to find the keys and read the stored rows. The
        table is read by neither: the branches of the push's conditional
        hold no operand of a table's shape."""
        # with the owner's plan of the keys it received nothing is
        # deduplicated
        merged = hash_lib.combine_keys(
            local,
            _mask_non_owned(self.spec, keys, me) if plan is None else None,
            grads, dedup_capacity=dedup_capacity, in_counts=counts,
            plan=None if plan is None else self.own(plan, me))
        if not carries:
            return merged
        uniq, valid = merged[:2]
        if resolved is not None:
            return merged + (resolved.rows, resolved.slot,
                             jnp.ones(valid.shape, bool))
        return merged + (
            hash_lib.init_rows(self.initializer, local.init_rng, uniq,
                               local.dim, local.weights.dtype),
            jnp.full(valid.shape, -1, jnp.int32),
            jnp.zeros(valid.shape, bool))

    def apply_merged(self, local, optimizer, merged, *, record_stats):
        """One find-or-insert and one sparse apply of what the push's
        branches merged, behind their conditional: with what a pull
        resolved (``known``) no key is looked for and no weight row read;
        the others are found here, and a stored row read, in loops that
        make no trip where every key is known."""
        uniq, valid, summed, counts, *carried = merged
        resolved = known = pulled = None
        if carried:
            rows, found, known = carried
            resolved = dedup.Resolution(rows=rows, slot=found)
        tkeys, failed, slot, inserted, fresh = hash_lib.place_keys(
            local, self.initializer, uniq, valid,
            max_probes=self.spec.max_probes, record_stats=record_stats,
            resolved=resolved, known=known)
        live = valid & (slot >= 0)
        a2a.record_stat("routed_owner_fresh_keys",
                        jnp.sum(inserted, dtype=jnp.int32), record_stats)
        if carried:
            @scope.stage("resolve")
            def stored_rows(weights, rows, slot, stored):
                read, _ = table_lib.read_distinct(weights, slot, stored)
                return jnp.where(stored[:, None], read, rows)

            # a key the table held and no pull resolved: its stored row
            pulled = stored_rows(local.weights, rows, slot,
                                 live & ~known & ~inserted)
            inserted = None
        weights, slots = table_lib.apply_rows(
            local.weights, local.slots, optimizer, slot, live, summed,
            counts, fresh, inserted, pulled=pulled,
            record_stats=record_stats)
        return (tkeys, failed), weights, slots

    def apply_local(self, local, optimizer, flat, grads, *, dedup_capacity,
                    record_stats, plan=None, resolved=None):
        me = lax.axis_index(self.spec.model_axis)
        # with the step's plan the mask falls on its distinct keys, and
        # the plan's slots are the unique buffer
        new = hash_lib.apply_gradients(
            local, optimizer, self.initializer,
            _mask_non_owned(self.spec, flat, me) if plan is None else None,
            grads, dedup_capacity=dedup_capacity,
            max_probes=self.spec.max_probes, record_stats=record_stats,
            plan=None if plan is None else self.own(plan, me),
            resolved=resolved)
        return (new.keys, new.insert_failures), new.weights, new.slots

    def outputs(self, carry, weights, slots, axes):
        keys, fails = carry
        # per-shard failure deltas -> replicated global total
        return keys, weights, slots, lax.psum(fails, axes)

    def ef_space(self, table) -> dict:
        sentinel, key_dtype = precision.ef_key_space(
            use_hash=True, wide=self.spec.wide, key_dtype=table.keys.dtype)
        return dict(wide=self.spec.wide, sentinel=sentinel,
                    key_dtype=key_dtype)


def _store(spec: HashShardingSpec, initializer: Any) -> HashStore:
    return HashStore(spec, make_initializer(initializer)
                     if initializer is not None else None)


def pull_sharded(state, indices: jnp.ndarray, initializer: Any, *,
                 mesh: Mesh, spec: HashShardingSpec,
                 batch_sharded: bool = True) -> jnp.ndarray:
    """:func:`sharded.pull_sharded` of a hash table (:class:`HashStore`
    has the contract): the owner shard resolves each key."""
    return sharded.pull_sharded(state, indices, mesh=mesh,
                                store=_store(spec, initializer),
                                batch_sharded=batch_sharded)


def apply_gradients_sharded(state, optimizer: SparseOptimizer,
                            initializer: Any, indices: jnp.ndarray,
                            grads: jnp.ndarray, *, mesh: Mesh,
                            spec: HashShardingSpec,
                            batch_sharded: bool = True,
                            dedup_capacity: Optional[int] = None):
    """:func:`sharded.apply_gradients_sharded` of a hash table: each key's
    grads reach its single owner shard, which inserts the keys it has not
    seen."""
    return sharded.apply_gradients_sharded(
        state, optimizer, indices, grads, mesh=mesh,
        store=_store(spec, initializer), batch_sharded=batch_sharded,
        dedup_capacity=dedup_capacity)

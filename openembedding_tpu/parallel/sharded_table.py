"""Vocabulary-sharded array tables: layout, creation, row delivery, and the
array store of the shared pull/push builder.

Layouts:
* ``mod``   (default, reference parity): global row r -> shard r % S, local
  row r // S. Robust to frequency-skewed sequential ids.
* ``div``   (block): r -> shard r // rows_per_shard. Matches NamedSharding's
  natural blocking; best when keys are pre-hashed (uniform).

The data planes (``ShardingSpec.plane``) and the pull / push programs every
plane variant runs are ``parallel/sharded.py``'s, shared with the hash
tables; :class:`ArrayStore` is what an array table puts behind them.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analysis import scope
from ..meta import EmbeddingVariableMeta
from ..ops import dedup
from ..optim.initializers import make_initializer
from ..optim.optimizers import SparseOptimizer, make_optimizer
from .. import table as table_lib
from . import alltoall as a2a
from . import hot_cache
from . import precision
from . import sharded
from .mesh import MODEL_AXIS


# every plane riding the owner-routed exchange layout (tables sharded
# over the whole mesh grid); "psum" is the lone broadcast-style ablation
A2A_PLANES = ("a2a", "a2a+cache", "a2a+grouped")
PLANES = A2A_PLANES + ("psum",)


@dataclasses.dataclass(frozen=True)
class ShardingSpec(sharded.PlaneSpec):
    """Static description of how one table is laid out on the mesh."""

    rows_per_shard: int
    layout: str = "mod"  # "mod" | "div"

    @property
    def padded_vocab(self) -> int:
        return self.num_shards * self.rows_per_shard

    def shard_and_local(self, idx: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if self.layout == "mod":
            return idx % self.num_shards, idx // self.num_shards
        return idx // self.rows_per_shard, idx % self.rows_per_shard

    def global_row(self, shard: jnp.ndarray, local: jnp.ndarray) -> jnp.ndarray:
        if self.layout == "mod":
            return local * self.num_shards + shard
        return shard * self.rows_per_shard + local


def make_sharding_spec(meta: EmbeddingVariableMeta, mesh: Mesh,
                       num_shards: int = -1, layout: str = "mod",
                       capacity: Optional[int] = None, plane: str = "a2a",
                       a2a_capacity: int = 0, a2a_slack: float = 2.0,
                       cache_k: int = 0, exchange_precision: str = "f32",
                       push_precision: str = "f32") -> ShardingSpec:
    """num_shards=-1 => one shard per device ("a2a") / per model slice ("psum").

    The reference's shard-per-server default (WorkerContext.cpp:66-85): on
    the a2a plane every chip is a "server", on the psum plane every model
    slice is one (its data-axis replicas mirror each other).

    ``plane="a2a+cache"`` is the a2a layout plus a ``cache_k``-row hot-row
    replica on every device (``parallel/hot_cache.py``); 0 picks the
    default size.

    A ``+bf16``/``+int8`` plane suffix (``parallel/precision.py``) is
    shorthand for the compressed-exchange rungs: it is parsed off the
    base plane into ``exchange_precision``/``push_precision``.
    """
    if layout not in ("mod", "div"):
        raise ValueError(f"unknown layout {layout!r}")
    plane, num_shards, cache_k, exchange_precision, push_precision = \
        _resolve_plane(mesh, plane, num_shards, cache_k, exchange_precision,
                       push_precision)
    vocab = capacity if capacity is not None else meta.vocabulary_size
    rows_per_shard = math.ceil(vocab / num_shards)
    return ShardingSpec(num_shards=num_shards, rows_per_shard=rows_per_shard,
                        layout=layout, plane=plane,
                        a2a_capacity=a2a_capacity, a2a_slack=a2a_slack,
                        cache_k=cache_k,
                        exchange_precision=exchange_precision,
                        push_precision=push_precision)


def _resolve_plane(mesh: Mesh, plane: str, num_shards: int, cache_k: int,
                   exchange_precision: str, push_precision: str):
    """What the array and hash spec builders settle alike: the base plane
    and the precision rungs its suffix names, the shard count that plane
    wants of this mesh, the size of the hot-row replica."""
    plane, exchange_precision, push_precision = _resolve_precision(
        plane, exchange_precision, push_precision)
    if plane not in PLANES:
        raise ValueError(f"unknown plane {plane!r}; known: {PLANES}")
    want = mesh.shape[MODEL_AXIS] if plane == "psum" else mesh.size
    if num_shards == -1:
        num_shards = want
    if num_shards != want:
        raise ValueError(
            f"num_shards={num_shards} must equal the {plane}-plane shard "
            f"count {want} for this mesh (or pass -1)")
    if plane != "a2a+cache":
        cache_k = 0
    elif cache_k <= 0:
        cache_k = hot_cache.DEFAULT_CACHE_K
    return plane, num_shards, cache_k, exchange_precision, push_precision


def _resolve_precision(plane: str, exchange_precision: str,
                       push_precision: str):
    """Fold a ``+bf16``/``+int8`` plane suffix into the precision fields
    and validate the combination (shared by array and hash spec
    builders)."""
    base, sep, spp = precision.parse_plane(plane)
    if (sep, spp) != ("f32", "f32"):
        for given, suffixed, knob in (
                (exchange_precision, sep, "exchange_precision"),
                (push_precision, spp, "push_precision")):
            if given not in ("f32", suffixed):
                raise ValueError(
                    f"plane {plane!r} implies {knob}={suffixed!r} but "
                    f"{given!r} was passed explicitly")
        exchange_precision, push_precision = sep, spp
    precision.check_spec_precision(base, exchange_precision,
                                   push_precision)
    return base, exchange_precision, push_precision


def create_sharded_table(meta: EmbeddingVariableMeta, optimizer: Any,
                         initializer: Any = None, *, mesh: Mesh,
                         spec: Optional[ShardingSpec] = None,
                         rng: Optional[jax.Array] = None,
                         wrap_cache: bool = True):
    """Materialize a table sharded over the mesh model axis.

    Each device initializes only its own rows (PRNG folded with the shard
    index) — no host-side full-table materialization, so tables bounded only
    by aggregate HBM, like the reference's tables bounded by aggregate PS RAM.
    """
    optimizer = make_optimizer(optimizer)
    initializer = make_initializer(initializer or table_lib.DEFAULT_INITIALIZER)
    if spec is None:
        spec = make_sharding_spec(meta, mesh)
    dtype = table_lib.resolve_dtype(meta)
    dim = meta.embedding_dim

    axes = spec.shard_axes
    sizes = tuple(mesh.shape[a] for a in axes)

    def _init(key):
        s = a2a.linear_shard_id(axes, sizes)
        k = jax.random.fold_in(key, s)
        weights = initializer.init(k, (spec.rows_per_shard, dim), dtype)
        slots = optimizer.init_slots(spec.rows_per_shard, dim, dtype)
        return table_lib.TableState(weights=weights, slots=slots)

    return _create(_init, table_state_specs(optimizer, dim, spec), mesh=mesh,
                   spec=spec, rng=rng, wrap_cache=wrap_cache)


def _create(init_shard, out_specs, *, mesh: Mesh, spec, rng, wrap_cache: bool):
    """Run ``init_shard(rng)`` on every shard: the state of a new table of
    either kind."""
    if rng is None:
        rng = jax.random.PRNGKey(0)
    fn = shard_map(init_shard, mesh=mesh, in_specs=(P(),),
                   out_specs=out_specs, check_vma=False)
    state = jax.jit(fn)(rng)
    if wrap_cache:
        # all-pad replica: zero hits (pure-a2a behavior) until the first
        # admission refresh (hot_cache.HotCacheManager / build_cache).
        # ``wrap_cache=False`` returns the bare table (callers composing
        # their own jitted init wrap eagerly afterwards).
        return hot_cache.attach_empty(state, spec, mesh)
    return state


def table_state_specs(optimizer: SparseOptimizer, dim: int,
                      spec: ShardingSpec):
    row = spec.row_spec()
    slot_spec = {name: row for name in optimizer.slot_shapes(dim)}
    return table_lib.TableState(weights=row, slots=slot_spec)


def state_specs(optimizer: SparseOptimizer, dim: int, spec: ShardingSpec):
    return with_cache_specs(table_state_specs(optimizer, dim, spec), spec)


def with_cache_specs(table, spec):
    """A table's specs (either kind) under those of its hot-row replica,
    where the plane has one."""
    if spec.is_cached:
        # the replica is replicated on every device
        return hot_cache.CachedState(
            table=table,
            cache=hot_cache.HotCacheState(
                keys=P(), rows=P(),
                slots={name: P() for name in table.slots}))
    return table


def state_shardings(state_specs, mesh: Mesh):
    return jax.tree.map(lambda p: NamedSharding(mesh, p), state_specs,
                        is_leaf=lambda x: isinstance(x, P))


@functools.lru_cache(maxsize=None)
def _filled_program(mesh: Mesh, spec: ShardingSpec, tail: tuple,
                    fill: float, dtype):
    row = spec.row_spec()
    shape = (spec.padded_vocab,) + tail
    return jax.jit(
        lambda: jnp.full(shape, fill, dtype=dtype),
        out_shardings=NamedSharding(mesh, row))


def filled_sharded(mesh: Mesh, spec: ShardingSpec, tail: tuple,
                   fill, dtype) -> jnp.ndarray:
    """A constant-filled [padded_vocab, *tail] array sharded per ``spec`` —
    the blank canvas the streaming checkpoint loader delivers rows onto."""
    return _filled_program(mesh, spec, tuple(tail), float(fill),
                           np.dtype(dtype).name)()


@functools.lru_cache(maxsize=None)
def _deliver_program(mesh: Mesh, spec: ShardingSpec, tail: tuple, dtype,
                     donate: bool = True):
    """Cached scatter program: place replicated (phys_row, value) chunks
    onto the owning device shards — the array-table twin of the hash
    loader's ``insert_rows_sharded`` chunk delivery, so a REMOTE checkpoint
    (sequential chunk stream, no memmap) loads with bounded host memory.
    ``donate=False`` keeps the input buffers alive (the serving hot-swap
    patches a COPY while in-flight readers keep the published state)."""
    rps = spec.rows_per_shard
    axes = spec.shard_axes
    sizes = tuple(mesh.shape[a] for a in axes)

    def _deliver(arr, phys, rows):
        me = a2a.linear_shard_id(axes, sizes)
        loc = phys - me * rps
        ok = (phys >= 0) & (loc >= 0) & (loc < rps)
        idx = jnp.where(ok, loc, rps).astype(jnp.int32)
        return arr.at[idx].set(rows.astype(arr.dtype), mode="drop")

    row = spec.row_spec()
    fn = shard_map(_deliver, mesh=mesh, in_specs=(row, P(), P()),
                   out_specs=row, check_vma=False)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def deliver_rows_sharded(arr: jnp.ndarray, phys: jnp.ndarray,
                         rows: jnp.ndarray, *, mesh: Mesh,
                         spec: ShardingSpec,
                         donate: bool = True) -> jnp.ndarray:
    """Scatter rows at PHYSICAL positions into a sharded array.

    ``phys``/``rows`` are replicated host chunks (phys = shard *
    rows_per_shard + local; -1 = padding). Chunks of one size reuse one
    compiled program. The checkpoint loader donates (the blank canvas is
    dead after delivery); the serving hot-swap passes ``donate=False`` so
    readers holding the pre-swap state never see a deleted buffer.
    """
    fn = _deliver_program(mesh, spec, tuple(rows.shape[1:]),
                          np.dtype(arr.dtype).name, donate)
    return fn(arr, phys, rows)


@functools.lru_cache(maxsize=None)
def _snapshot_program(mesh: Mesh, spec: ShardingSpec, arrays: int):
    """Cached gather program of :func:`snapshot_rows_sharded`; one
    compile a staging length."""
    rps = spec.rows_per_shard
    axes = spec.shard_axes
    sizes = tuple(mesh.shape[a] for a in axes)

    def ckpt_gather(arrays, phys, count):
        me = a2a.linear_shard_id(axes, sizes)
        loc = phys - me * rps
        ok = (phys >= 0) & (loc >= 0) & (loc < rps)
        # a row another shard owns reads as zeros here: index rps is
        # past this shard's end
        staged = table_lib.snapshot_rows(
            arrays, jnp.where(ok, loc, rps).astype(jnp.int32), count)
        if spec.num_shards > 1:
            staged = [lax.psum(s, axes) for s in staged]
        return staged

    row = spec.row_spec()
    fn = shard_map(ckpt_gather, mesh=mesh,
                   in_specs=([row] * arrays, P(), P()),
                   out_specs=[P()] * arrays, check_vma=False)
    return jax.jit(fn)


def snapshot_rows_sharded(arrays, phys: jnp.ndarray, count, *, mesh: Mesh,
                          spec: ShardingSpec):
    """Gather the rows at PHYSICAL positions ``phys[:count]`` of every
    sharded array of ``arrays`` into replicated staging buffers of
    ``phys``'s length: the read twin of :func:`deliver_rows_sharded`, and
    a delta checkpoint's snapshot (``table.snapshot_rows``). Nothing is
    donated and nothing waits: the program runs behind whatever was
    dispatched before it."""
    return _snapshot_program(mesh, spec, len(arrays))(
        list(arrays), phys, jnp.asarray(count, jnp.int32))


def _masked_local(spec: ShardingSpec, flat: jnp.ndarray, me=None):
    """``(owned [n], local row [n])`` of ``flat`` on shard ``me``; None:
    this model-axis shard (the masked-local body of the psum plane).
    Invalid indices (negative or beyond the padded vocab) are owned by
    nobody: the pull's psum returns zero rows for them, like
    ``table_lib.pull``."""
    s = lax.axis_index(spec.model_axis) if me is None else me
    shard, local = spec.shard_and_local(flat)
    owned = (shard == s) & (flat >= 0) & (flat < spec.padded_vocab)
    return owned, local


def _take_owned(weights, owned, local):
    rows = jnp.take(weights, jnp.where(owned, local, 0), axis=0, mode="clip")
    return jnp.where(owned[:, None], rows, jnp.zeros_like(rows))


@dataclasses.dataclass(frozen=True)
class ArrayStore:
    """An array table behind ``parallel/sharded.py``'s builder (which lists
    what a store answers): keys are row ids, a key's slot is its local row,
    and merging changes nothing but the rows, so the carry is empty."""

    spec: ShardingSpec
    prefix = ""
    key_bytes = 4

    def operands(self, table):
        return table.weights, table.slots

    def specs(self, slot_names: tuple):
        row = self.spec.row_spec()
        return row, {name: row for name in slot_names}

    def local(self, weights, slots):
        return table_lib.TableState(weights=weights, slots=slots)

    def rebuild(self, table, outs):
        return self.local(*outs)

    def batch_shape(self, shape: tuple) -> tuple:
        return shape

    def sentinel(self, dtype):
        return dedup.FILL

    def valid(self, flat):
        return (flat >= 0) & (flat < self.spec.padded_vocab)

    def owner(self, keys):
        shard, _ = self.spec.shard_and_local(keys)
        return jnp.where(self.valid(keys), shard,
                         self.spec.num_shards).astype(jnp.int32)

    def _mine(self, keys, me):
        shard, local = self.spec.shard_and_local(keys)
        return self.valid(keys) & (shard == me), local

    def slot_of(self, carry, keys, me):
        mine, local = self._mine(keys, me)
        return jnp.where(mine, local, -1)

    def resolve(self, local, keys, me):
        return _take_owned(local.weights, *self._mine(keys, me))

    def read_local(self, local, flat):
        owned, row = scope.stage("route")(
            lambda flat: _masked_local(self.spec, flat))(flat)
        return scope.stage("resolve")(_take_owned)(local.weights, owned, row)

    def routing(self) -> tuple:
        """What :meth:`owner` reads of the spec: two stores that agree on
        it send one column's keys to the same owners."""
        return (self.spec.layout, self.spec.num_shards,
                self.spec.rows_per_shard)

    def own(self, plan: dedup.Plan, me=None) -> dedup.Plan:
        """``plan`` as shard ``me`` sees it (None: this model-axis shard):
        a key it owns is its local row, any other -1 and not valid."""
        @scope.stage("route")
        def mask(uniq, valid, *me):
            owned, row = _masked_local(self.spec, uniq, *me)
            return jnp.where(owned, row, -1), valid & owned

        uniq, valid = mask(plan.uniq, plan.valid,
                           *(() if me is None else (me,)))
        return plan.replace(uniq=uniq, valid=valid)

    def read_plan(self, local, plan, record_stats, me=None):
        mine = self.own(plan, me)

        @scope.stage("resolve")
        def read(weights, row, live):
            rows, walked = table_lib.read_distinct(weights, row, live)
            table_lib.record_pull(live, walked, plan.inverse.shape[0],
                                  record_stats)
            return dedup.Resolution(rows=rows)

        return read(local.weights, mine.uniq, mine.valid)

    def merge(self, local, keys, grads, counts, me, *, dedup_capacity,
              plan=None, resolved=None, carries=False):
        if plan is not None:
            # the owner's plan of the keys it received: its slots are the
            # buffer, and ``resolved`` (their rows) is the apply's to take
            merged = table_lib.merge_gradients(
                None, grads, plan=self.own(plan, me))
            return merged if resolved is None else merged + (resolved.rows,)
        rows = scope.stage("route")(
            lambda keys, me: self.slot_of((), keys, me))(keys, me)
        merged = table_lib.merge_gradients(
            rows, grads, dedup_capacity=dedup_capacity, in_counts=counts)
        # a step round 1 did not hold reads its weight rows here, so that
        # one apply follows both branches
        return merged + (table_lib.pulled_rows(local.weights, *merged[:2]),) \
            if carries else merged

    def apply_merged(self, local, optimizer, merged, *, record_stats):
        rows, live, summed, counts, *pulled = merged
        weights, slots = table_lib.apply_rows(
            local.weights, local.slots, optimizer, rows, live, summed,
            counts, pulled=pulled[0] if pulled else None,
            record_stats=record_stats)
        return (), weights, slots

    def apply_local(self, local, optimizer, flat, grads, *, dedup_capacity,
                    record_stats, plan=None, resolved=None):
        @scope.stage("route")
        def mask(flat):
            owned, row = _masked_local(self.spec, flat)
            # non-owned entries become index -1 -> dropped in
            # apply_gradients
            return jnp.where(owned, row, -1)

        # with the step's plan the mask falls on its distinct keys, and
        # the plan's slots are the unique buffer
        new = table_lib.apply_gradients(
            local, optimizer, mask(flat) if plan is None else None, grads,
            dedup_capacity=dedup_capacity, record_stats=record_stats,
            plan=None if plan is None else self.own(plan),
            resolved=resolved)
        return (), new.weights, new.slots

    def outputs(self, carry, weights, slots, axes):
        return weights, slots

    def ef_space(self, table) -> dict:
        sentinel, key_dtype = precision.ef_key_space(use_hash=False)
        return dict(wide=False, sentinel=sentinel, key_dtype=key_dtype)


def pull_sharded(state, indices: jnp.ndarray, *, mesh: Mesh,
                 spec: ShardingSpec, batch_sharded: bool = True
                 ) -> jnp.ndarray:
    """:func:`sharded.pull_sharded` of an array table: a gather + one psum
    over ICI on the masked-local body, the owner-routed exchange
    elsewhere."""
    return sharded.pull_sharded(state, indices, mesh=mesh,
                                store=ArrayStore(spec),
                                batch_sharded=batch_sharded)


def apply_gradients_sharded(state, optimizer: SparseOptimizer,
                            indices: jnp.ndarray, grads: jnp.ndarray, *,
                            mesh: Mesh, spec: ShardingSpec,
                            batch_sharded: bool = True,
                            dedup_capacity: Optional[int] = None):
    """:func:`sharded.apply_gradients_sharded` of an array table."""
    return sharded.apply_gradients_sharded(
        state, optimizer, indices, grads, mesh=mesh, store=ArrayStore(spec),
        batch_sharded=batch_sharded, dedup_capacity=dedup_capacity)

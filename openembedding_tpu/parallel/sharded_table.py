"""Vocabulary-sharded embedding tables over a device mesh.

TPU-native replacement for the reference's parameter-server data plane:

* The reference shards each variable's key space ``index % global_shard_num``
  across PS processes and pulls rows by RPC
  (/root/reference/openembedding/server/EmbeddingPullOperator.cpp:60-112,
  key stored as ``index / shard_num``). Here the same modulo layout shards
  rows across TPU devices along the mesh ``model`` axis, and the pull is a
  shard_map region: local gather of owned rows + ``psum`` over the model
  axis — XLA collectives over ICI instead of TCP/RDMA round trips.
* The push + store pipeline (client pre-reduce -> MpscGradientReducer ->
  EmbeddingStoreOperator commit, EmbeddingPushOperator.cpp:29-161,
  EmbeddingStoreOperator.cpp:23-81) becomes: ``all_gather`` of (indices,
  row-grads) over the data axis, then every model shard dedups/combines the
  global batch, masks ownership, and applies its rows' optimizer update
  locally — one fused XLA program, synchronous per step (the reference's
  fake-gradient batch barrier is unnecessary: the SPMD step IS the barrier).
* ``num_shards`` semantics: the reference's shard-per-server default
  (WorkerContext.cpp:66-85) corresponds to one shard per mesh model slice.

Layouts:
* ``mod``   (default, reference parity): global row r -> shard r % S, local
  row r // S. Robust to frequency-skewed sequential ids.
* ``div``   (block): r -> shard r // rows_per_shard. Matches NamedSharding's
  natural blocking; best when keys are pre-hashed (uniform).

Data planes (``ShardingSpec.plane``):
* ``"a2a"`` (default) — owner-routed all-to-all exchange (see
  ``parallel/alltoall.py``): tables sharded over the WHOLE mesh (data x
  model), per-device traffic O(batch_slice * dim). The reference's
  dedup->shard->request->scatter pipeline, TPU-native.
* ``"psum"`` — tables sharded over the model axis only (replicated across
  the data axis); pull = gather + psum, push = all_gather + masked local
  update. Simpler program, more ICI bytes and D-fold HBM replication; kept
  as the ablation baseline and for meshes where replicas are wanted.
* ``"a2a+cache"`` — the a2a layout plus a frequency-tracked top-K hot-row
  replica in every device's HBM (``parallel/hot_cache.py``): pulls for hot
  keys are served locally with no exchange round, pushes pre-reduce
  locally and merge with one psum over the K cached rows — exactly
  equivalent to ``"a2a"``, built for Zipfian key streams.
* ``"a2a+grouped"`` — the a2a layout, but the COLLECTION batches all
  same-shape tables into one exchange per group per step
  (``parallel/grouped.py``): a T-table model pays O(#groups) collective
  rounds instead of O(T). Per-table calls on this plane (serving probes,
  checkpoint paths) behave exactly like ``"a2a"``.
* ``"a2a+pipelined"`` — the a2a layout, but the TRAINER double-buffers
  the exchange (``parallel/pipelined.py``): batch N+1's rows are pulled
  inside step N's jitted program (after step N's push commits, so
  results stay bit-identical to ``"a2a"``) and the pull's index/key-leg
  collectives overlap step N's dense compute. Per-table calls behave
  exactly like ``"a2a"`` — the plane only changes the step schedule.
* ``"a2a+grouped+pipelined"`` — both: grouped collection-level exchange
  AND the pipelined step schedule, so the prefetched exchange is one
  collective round per GROUP.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analysis import scope
from ..meta import EmbeddingVariableMeta
from ..ops import dedup
from ..utils import observability
from ..optim.initializers import make_initializer
from ..optim.optimizers import SparseOptimizer, make_optimizer
from .. import table as table_lib
from . import alltoall as a2a
from . import hot_cache
from . import precision
from .mesh import DATA_AXIS, MODEL_AXIS


# every plane riding the owner-routed exchange layout (tables sharded
# over the whole mesh grid); "psum" is the lone broadcast-style ablation
A2A_PLANES = ("a2a", "a2a+cache", "a2a+grouped", "a2a+pipelined",
              "a2a+grouped+pipelined")
PLANES = A2A_PLANES + ("psum",)


@dataclasses.dataclass(frozen=True)
class ShardingSpec:
    """Static description of how one table is laid out on the mesh."""

    num_shards: int
    rows_per_shard: int
    layout: str = "mod"  # "mod" | "div"
    data_axis: str = DATA_AXIS
    model_axis: str = MODEL_AXIS
    plane: str = "a2a"   # "a2a" | "psum" | "a2a+cache" | "a2a+grouped"
                         # | "a2a+pipelined" | "a2a+grouped+pipelined"
    a2a_capacity: int = 0    # per-destination bucket rows; 0 = auto
    a2a_slack: float = 2.0   # auto capacity = slack * mean bucket size
    cache_k: int = 0         # hot-row replica slots ("a2a+cache" plane)
    # compressed-exchange rungs (parallel/precision.py): pulled rows /
    # pushed pre-reduced grads on the wire; master weights + optimizer
    # slots stay at the table's storage dtype in the shard
    exchange_precision: str = "f32"   # "f32" | "bf16"
    push_precision: str = "f32"       # "f32" | "bf16" | "int8_ef"

    @property
    def is_cached(self) -> bool:
        return self.plane == "a2a+cache"

    @property
    def plane_label(self) -> str:
        """Observable plane token incl. the precision suffix — keys the
        HLO module names, plane_timed spans, contract registry and the
        graftscope byte ledger (``precision.plane_label``)."""
        return precision.plane_label(self.plane, self.exchange_precision,
                                     self.push_precision)

    @property
    def pull_wire_dtype(self):
        return precision.wire_dtype(self.exchange_precision)

    @property
    def push_wire_dtype(self):
        # int8_ef carries its own int8 payload inside exchange_push
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
        """Mesh axes the table's row dimension is sharded over."""
        if self.plane != "psum":
            return (self.data_axis, self.model_axis)
        return (self.model_axis,)

    def row_spec(self) -> P:
        return P(self.shard_axes)

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
                       capacity: Optional[int] = None,
                       plane: str = "a2a",
                       a2a_capacity: int = 0,
                       a2a_slack: float = 2.0,
                       cache_k: int = 0,
                       exchange_precision: str = "f32",
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
    plane, exchange_precision, push_precision = _resolve_precision(
        plane, exchange_precision, push_precision)
    if plane not in PLANES:
        raise ValueError(f"unknown plane {plane!r}")
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
    vocab = capacity if capacity is not None else meta.vocabulary_size
    rows_per_shard = math.ceil(vocab / num_shards)
    return ShardingSpec(num_shards=num_shards, rows_per_shard=rows_per_shard,
                        layout=layout, plane=plane,
                        a2a_capacity=a2a_capacity, a2a_slack=a2a_slack,
                        cache_k=cache_k,
                        exchange_precision=exchange_precision,
                        push_precision=push_precision)


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


def create_sharded_table(meta: EmbeddingVariableMeta,
                         optimizer: Any,
                         initializer: Any = None,
                         *,
                         mesh: Mesh,
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
    if rng is None:
        rng = jax.random.PRNGKey(0)
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


def table_state_specs(optimizer: SparseOptimizer, dim: int,
                      spec: ShardingSpec):
    row = spec.row_spec()
    slot_spec = {name: row for name in optimizer.slot_shapes(dim)}
    return table_lib.TableState(weights=row, slots=slot_spec)


def state_specs(optimizer: SparseOptimizer, dim: int, spec: ShardingSpec):
    table = table_state_specs(optimizer, dim, spec)
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


def _masked_local(spec: ShardingSpec, flat: jnp.ndarray):
    """``(owned [n], local row [n])`` of ``flat`` on this model-axis shard
    (the masked-local body of the psum plane). Invalid indices (negative
    or beyond the padded vocab) are owned by nobody: the pull's psum
    returns zero rows for them, like ``table_lib.pull``."""
    s = lax.axis_index(spec.model_axis)
    shard, local = spec.shard_and_local(flat)
    owned = (shard == s) & (flat >= 0) & (flat < spec.padded_vocab)
    return owned, local


@functools.lru_cache(maxsize=None)
def _pull_program(mesh: Mesh, spec: ShardingSpec, dim: int,
                  batch_sharded: bool, record_stats: bool = False):
    """Cached jitted pull: eager callers (serving lookups, tests) would
    otherwise rebuild + retrace the shard_map closure every call."""
    batch_spec = P(spec.data_axis) if batch_sharded else P()

    # single shard => nothing to route; the masked-local body below (whose
    # collectives are free over size-1 axes) skips the bucketing machinery
    # (~25% faster on one chip for the headline config). The cached plane
    # always routes: its residue masking composes with the exchange. A
    # grouped-plane table addressed PER TABLE (serving probes, checkpoint
    # paths) takes the plain a2a program — grouping only exists at the
    # collection level.
    if (spec.plane != "psum" and spec.num_shards > 1) \
            or spec.is_cached:
        grid_axes, grid_sizes, split_axes, split_sizes = a2a.grid_info(
            mesh, spec.shard_axes, spec.model_axis, batch_sharded)
        sentinel = dedup.FILL

        def _pull_core(weights, idx):
            me = a2a.linear_shard_id(grid_axes, grid_sizes)

            def resolve(keys):
                shard, local = spec.shard_and_local(keys)
                mine = ((keys >= 0) & (keys < spec.padded_vocab)
                        & (shard == me))
                rows = jnp.take(weights, jnp.where(mine, local, 0), axis=0,
                                mode="clip")
                return jnp.where(mine[:, None], rows, jnp.zeros_like(rows))

            def owner(keys):
                shard, _ = spec.shard_and_local(keys)
                valid = (keys >= 0) & (keys < spec.padded_vocab)
                return jnp.where(valid, shard, spec.num_shards).astype(
                    jnp.int32)

            rows = a2a.exchange_pull(
                idx.ravel(), resolve, owner, sentinel=sentinel, dim=dim,
                num_shards=spec.num_shards, grid_axes=grid_axes,
                grid_sizes=grid_sizes, split_axes=split_axes,
                split_sizes=split_sizes, capacity=spec.a2a_capacity,
                slack=spec.a2a_slack, record_stats=record_stats,
                wire_dtype=spec.pull_wire_dtype)
            return rows.reshape(idx.shape + (dim,))

        if spec.is_cached:
            def _pull(weights, ckeys, crows, idx):
                flat = idx.ravel()
                valid = (flat >= 0) & (flat < spec.padded_vocab)
                pos, hit = hot_cache.lookup(ckeys, flat, valid)
                served = jnp.where(hit[:, None],
                                   jnp.take(crows, pos, axis=0),
                                   jnp.zeros((1, dim), crows.dtype))
                hot_cache.record_cache_stats(
                    hit, valid,
                    entry_bytes=dim * crows.dtype.itemsize + 4,
                    split_axes=split_axes, split_sizes=split_sizes,
                    record=record_stats)
                resid = hot_cache.mask_hits(flat, hit, sentinel)
                rows = _pull_core(weights, resid).reshape(-1, dim)
                return (rows + served).reshape(idx.shape + (dim,))
        else:
            _pull = _pull_core
    else:
        def _pull(weights, idx):
            owned, local = scope.stage("route")(
                lambda flat: _masked_local(spec, flat))(idx.ravel())

            @scope.stage("resolve")
            def read(weights, owned, local):
                rows = jnp.take(weights, jnp.where(owned, local, 0), axis=0,
                                mode="clip")
                return jnp.where(owned[:, None], rows, jnp.zeros_like(rows))

            rows = scope.stage("exchange")(
                lambda rows: lax.psum(rows, spec.model_axis))(
                    read(weights, owned, local))
            return rows.reshape(idx.shape + (dim,))

    if spec.is_cached:
        in_specs = (spec.row_spec(), P(), P(), batch_spec)
    else:
        in_specs = (spec.row_spec(), batch_spec)
    # plane-identifiable HLO module name (jit names the module after the
    # callable): a contract-audit failure then says WHICH plane's
    # program regressed (analysis/contracts.py); compressed planes carry
    # their precision suffix (pull_a2a_bf16, ...)
    _pull.__name__ = f"pull_{spec.plane_label.replace('+', '_')}"
    fn = shard_map(_pull, mesh=mesh,
                   in_specs=in_specs,
                   out_specs=batch_spec,
                   check_vma=False)
    return jax.jit(fn)


def pull_sharded(state,
                 indices: jnp.ndarray,
                 *,
                 mesh: Mesh,
                 spec: ShardingSpec,
                 batch_sharded: bool = True) -> jnp.ndarray:
    """Distributed embedding lookup.

    ``indices``: any shape, sharded over the data axis on dim 0 when
    ``batch_sharded`` (the normal training path) else replicated. Returns
    rows with the same batch sharding. Equivalent to the reference's pull
    RPC fan-out + response scatter (EmbeddingPullOperator.cpp:40-252), as a
    gather + one psum over ICI. On the ``"a2a+cache"`` plane ``state`` is a
    :class:`hot_cache.CachedState`; hot keys are served from the local
    replica and only the residue rides the exchange.
    """
    record = observability.evaluate_performance()
    if spec.is_cached:
        dim = state.table.weights.shape[-1]
        fn = _pull_program(mesh, spec, dim, batch_sharded, record)
        return observability.plane_timed(
            "pull", spec.plane_label, record, fn, state.table.weights,
            state.cache.keys, state.cache.rows, indices)
    # int8_ef states wrap the table with the push residual; pulls read
    # through the wrapper (serving restores may hand a bare table)
    state = precision.unwrap(state)
    dim = state.weights.shape[-1]
    fn = _pull_program(mesh, spec, dim, batch_sharded, record)
    return observability.plane_timed("pull", spec.plane_label, record, fn,
                                     state.weights, indices)


@functools.lru_cache(maxsize=None)
def _apply_program(mesh: Mesh, spec: ShardingSpec,
                   optimizer: SparseOptimizer, dim: int,
                   batch_sharded: bool, dedup_capacity: Optional[int],
                   slot_names: tuple, record_stats: bool = False):
    batch_spec = P(spec.data_axis) if batch_sharded else P()

    if (spec.plane != "psum" and spec.num_shards > 1) \
            or spec.is_cached:
        grid_axes, grid_sizes, split_axes, split_sizes = a2a.grid_info(
            mesh, spec.shard_axes, spec.model_axis, batch_sharded)

        def _push_core(weights, slots, flat, g2, ef=None):
            me = a2a.linear_shard_id(grid_axes, grid_sizes)

            def owner(keys):
                shard, _ = spec.shard_and_local(keys)
                valid = (keys >= 0) & (keys < spec.padded_vocab)
                return jnp.where(valid, shard, spec.num_shards).astype(
                    jnp.int32)

            def merge_fn(st, keys, grads, counts):
                @scope.stage("route")
                def mask(keys, me):
                    shard, local = spec.shard_and_local(keys)
                    mine = ((keys >= 0) & (keys < spec.padded_vocab)
                            & (shard == me))
                    return jnp.where(mine, local, -1)

                return st, table_lib.merge_gradients(
                    mask(keys, me), grads, dedup_capacity=dedup_capacity,
                    in_counts=counts)

            out = a2a.exchange_push(
                flat, g2, (), merge_fn, owner,
                sentinel=dedup.FILL, num_shards=spec.num_shards,
                grid_axes=grid_axes, grid_sizes=grid_sizes,
                split_axes=split_axes, split_sizes=split_sizes,
                capacity=spec.a2a_capacity, slack=spec.a2a_slack,
                record_stats=record_stats,
                wire_dtype=spec.push_wire_dtype, ef_state=ef)
            (_, merged), new_ef = out if ef is not None else (out, None)
            table = table_lib.apply_rows(weights, slots, optimizer, *merged,
                                         record_stats=record_stats)
            return table if ef is None else (table, new_ef)

        if spec.is_cached:
            def _apply(weights, slots, ckeys, crows, cslots, idx, g):
                me = a2a.linear_shard_id(grid_axes, grid_sizes)
                flat = idx.ravel()
                g2 = g.reshape(-1, dim)
                valid = (flat >= 0) & (flat < spec.padded_vocab)
                pos, hit = hot_cache.lookup(ckeys, flat, valid)
                k = ckeys.shape[0]
                summed, counts = hot_cache.cache_pre_reduce(
                    pos, hit, g2, k, split_axes, split_sizes, grid_axes)
                hot_cache.record_cache_stats(
                    hit, valid,
                    entry_bytes=dim * crows.dtype.itemsize + 8,
                    split_axes=split_axes, split_sizes=split_sizes,
                    record=record_stats)
                # residue rides the exchange with hits masked invalid
                resid = hot_cache.mask_hits(flat, hit, dedup.FILL)
                weights, slots = _push_core(weights, slots, resid, g2)
                # identical psum'd totals on every device -> identical
                # replica update everywhere; the owner scatters its rows
                # back so the table stays authoritative
                cache = hot_cache.HotCacheState(keys=ckeys, rows=crows,
                                                slots=cslots)
                cache = hot_cache.update_replica(optimizer, cache, summed,
                                                 counts)
                shard, local = spec.shard_and_local(ckeys)
                ckv = (ckeys >= 0) & (ckeys < spec.padded_vocab)
                mine = ckv & (shard == me) & (counts > 0)
                oob = jnp.asarray(spec.rows_per_shard, local.dtype)
                sc = jnp.where(mine, local, oob)
                weights = weights.at[sc].set(
                    cache.rows.astype(weights.dtype), mode="drop")
                slots = {name: slots[name].at[sc].set(
                    cache.slots[name].astype(slots[name].dtype),
                    mode="drop") for name in slots}
                return weights, slots, cache.rows, cache.slots
        elif spec.is_int8_ef:
            def _apply(weights, slots, ef_keys, ef_resid, idx, g):
                (weights, slots), (nek, ner) = _push_core(
                    weights, slots, idx.ravel(), g.reshape(-1, dim),
                    ef=(ef_keys, ef_resid))
                return weights, slots, nek, ner
        else:
            def _apply(weights, slots, idx, g):
                return _push_core(weights, slots, idx.ravel(),
                                  g.reshape(-1, dim))
    else:
        def _apply(weights, slots, idx, g):
            flat = idx.ravel()
            g2 = g.reshape(-1, dim)
            if batch_sharded:
                flat, g2 = scope.stage("exchange")(
                    lambda *xs: tuple(lax.all_gather(x, spec.data_axis,
                                                     tiled=True)
                                      for x in xs))(flat, g2)

            @scope.stage("route")
            def mask(flat):
                owned, local = _masked_local(spec, flat)
                # non-owned entries become index -1 -> dropped in
                # apply_gradients
                return jnp.where(owned, local, -1)

            masked = mask(flat)
            local_state = table_lib.TableState(weights=weights, slots=slots)
            new_state = table_lib.apply_gradients(
                local_state, optimizer, masked, g2,
                dedup_capacity=dedup_capacity, record_stats=record_stats)
            return new_state.weights, new_state.slots

    slot_specs = {name: spec.row_spec() for name in slot_names}
    _apply.__name__ = f"push_{spec.plane_label.replace('+', '_')}"
    if spec.is_cached:
        cache_slot_specs = {name: P() for name in slot_names}
        fn = shard_map(_apply, mesh=mesh,
                       in_specs=(spec.row_spec(), slot_specs, P(), P(),
                                 cache_slot_specs, batch_spec, batch_spec),
                       out_specs=(spec.row_spec(), slot_specs, P(),
                                  cache_slot_specs),
                       check_vma=False)
    elif spec.is_int8_ef and spec.num_shards > 1:
        # the EF residual buffers shard over the exchange grid: each
        # device owns exactly its sender slice's block
        ef_spec = P(spec.shard_axes)
        fn = shard_map(_apply, mesh=mesh,
                       in_specs=(spec.row_spec(), slot_specs, ef_spec,
                                 ef_spec, batch_spec, batch_spec),
                       out_specs=(spec.row_spec(), slot_specs, ef_spec,
                                  ef_spec),
                       check_vma=False)
    else:
        fn = shard_map(_apply, mesh=mesh,
                       in_specs=(spec.row_spec(), slot_specs, batch_spec,
                                 batch_spec),
                       out_specs=(spec.row_spec(), slot_specs),
                       check_vma=False)
    return jax.jit(fn)


def apply_gradients_sharded(state,
                            optimizer: SparseOptimizer,
                            indices: jnp.ndarray,
                            grads: jnp.ndarray,
                            *,
                            mesh: Mesh,
                            spec: ShardingSpec,
                            batch_sharded: bool = True,
                            dedup_capacity: Optional[int] = None):
    """Distributed push+update: every shard applies its owned rows.

    Data-axis devices all_gather the global (indices, grads) so the update is
    computed identically on every data replica of a model shard — replacing
    the reference's single-owner store RPC (WorkerContext.cpp:115-123) with
    deterministic replicated application. On the ``"a2a+cache"`` plane
    ``state`` is a :class:`hot_cache.CachedState`: hot keys pre-reduce
    locally + one psum over the K replica rows (no exchange for them), and
    the owner writes the updated rows back so the table stays authoritative.
    """
    optimizer = make_optimizer(optimizer)
    record = observability.evaluate_performance()
    if spec.is_cached:
        table = state.table
        dim = table.weights.shape[-1]
        fn = _apply_program(mesh, spec, optimizer, dim, batch_sharded,
                            dedup_capacity, tuple(table.slots), record)
        weights, slots, crows, cslots = observability.plane_timed(
            "push", spec.plane_label, record, fn,
            table.weights, table.slots, state.cache.keys, state.cache.rows,
            state.cache.slots, indices, grads)
        return hot_cache.CachedState(
            table=table_lib.TableState(weights=weights, slots=slots),
            cache=hot_cache.HotCacheState(keys=state.cache.keys,
                                          rows=crows, slots=cslots))
    if spec.is_int8_ef and spec.num_shards > 1:
        dim = precision.unwrap(state).weights.shape[-1]
        sentinel, key_dtype = precision.ef_key_space(use_hash=False)
        table, ef_keys, ef_resid = precision.ensure_ef(
            state, dim=dim, wide=False, sentinel=sentinel,
            n_flat=int(np.prod(indices.shape)),
            data=mesh.shape[spec.data_axis],
            model=mesh.shape[spec.model_axis],
            batch_sharded=batch_sharded, key_dtype=key_dtype)
        fn = _apply_program(mesh, spec, optimizer, dim, batch_sharded,
                            dedup_capacity, tuple(table.slots), record)
        weights, slots, nek, ner = observability.plane_timed(
            "push", spec.plane_label, record, fn,
            table.weights, table.slots, ef_keys, ef_resid, indices, grads)
        return precision.EFState(
            table=table_lib.TableState(weights=weights, slots=slots),
            keys=nek, resid=ner)
    state = precision.unwrap(state)
    dim = state.weights.shape[-1]
    fn = _apply_program(mesh, spec, optimizer, dim, batch_sharded,
                        dedup_capacity, tuple(state.slots), record)
    weights, slots = observability.plane_timed(
        "push", spec.plane_label, record, fn,
        state.weights, state.slots, indices, grads)
    return table_lib.TableState(weights=weights, slots=slots)

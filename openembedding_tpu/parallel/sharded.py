"""Sharded embedding tables over a device mesh: the one pull/push builder.

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

Data planes (``PlaneSpec.plane``, shared by array and hash tables):
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

One builder, two stores. Every plane variant (routed, masked-local,
hot-cache, int8-EF) is written here once; what differs between an array
table and a hash table sits behind a *store*: a small frozen (hashable: it
keys the program caches) object next to the state it understands,
``sharded_table.ArrayStore`` and ``sharded_hash.HashStore``. A store
answers:

* ``spec`` (a :class:`PlaneSpec`), ``prefix`` (of the program's name),
  ``key_bytes`` (of a key on the wire, for the cache statistics);
* ``operands(table)`` — the arrays of the state that enter a program,
  ``specs(slot_names)`` — their PartitionSpecs (a program's table outputs
  are laid out like its operands), ``local(*operands)`` — the kind's
  per-shard state inside ``shard_map`` (with ``.weights`` / ``.slots``),
  ``rebuild(table, outs)`` — a program's outputs as a state again;
* ``batch_shape(idx.shape)``, ``sentinel(dtype)``, ``valid(flat)``,
  ``owner(keys)`` — the key space;
* ``resolve(local, keys, me)`` / ``read_local(local, flat)`` — the pull's
  owner side behind the exchange / on the masked-local body, where
  ``read_plan(local, plan, record_stats, me)`` reads one row a distinct
  key of the step's plan (below) and returns what it resolved, a
  ``dedup.Resolution`` of the plan's slots under shard ``me``'s ownership
  mask (None: this model-axis shard): the rows, and for a kind that
  probes the slots it found;
* ``merge(local, keys, grads, counts, me, ..., plan=, resolved=,
  carries=)`` then ``apply_merged(local, optimizer, merged, ...)`` /
  ``apply_local(local, optimizer, flat, grads, ..., plan=, resolved=)`` —
  the push's owner side likewise: ``merge`` runs inside a branch of the
  exchange's conditional and writes nothing (distinct keys, summed
  gradients, counts), ``apply_merged`` writes the table after it (a hash
  table's one find-or-insert, the sparse apply) and returns ``(carry,
  weights, slots)`` as ``apply_local`` does. ``resolved`` is
  ``read_plan``'s of the same plan and the same table contents, which the
  push then takes instead of resolving again; ``carries`` says a
  resolution goes with the push, so that a merge that is handed none (the
  gathered branch) returns the same structure. ``outputs(carry, weights,
  slots, axes)`` — what leaves the program, ``slot_of(carry, keys, me)``
  — a key's slot in this shard (-1: not here), where the owner writes a
  cached key's row back;
* ``routing()`` — what ``owner`` reads of the spec (hashable): stores that
  agree on it route one column alike, and share its routed plan;
* ``ef_space(table)`` — the int8-EF residual's key space.

No code in this file asks which kind it holds: a step that needs to is a
method the store lacks.

One dedup a distinct id column a step. The masked-local body looks every
position of a batch up, and the push behind it dedups the same keys; the
train step (``Trainer``) therefore builds a column's :class:`dedup.Plan`
once (:func:`plan_sharded`) and hands it to the pull, which resolves the
distinct keys over their occupied prefix and expands by ``inverse``, and to
the push, whose unique buffer it is. It serves both where both see the
same keys (:func:`shares_plan`). The routed body has the step's plan too,
an ``alltoall.RoutedPlan``, on the plain ``a2a`` plane: everything about a
column that needs no table and no gradient, in the plan's program. At the
sender the device's slice of the batch, its dedup, the owners and round
1's buckets (one unique and one bucketing a column, where pull and push of
every table made their own); then the key all-to-all; at the owner one
dedup of the bucket slots it received. The owner's pull and push are the
masked-local body's over that plan (``store.read_plan``,
``store.merge(plan=, resolved=)`` then ``store.apply_merged``): a row read
a distinct key, the rows laid back into bucket order by ``inverse``, one
combine a table, one find-or-insert a hash table behind the push's
conditional. What
round 1 did not hold runs as it does without a plan (the pull's residue
rounds, the push's gathered branch), and so does any call without one:
the cached and grouped planes, an ``int8_ef`` push, serving,
``eval_step``.
One resolve a key a step, too: the planned pull returns, beside the rows,
what each shard resolved for the plan's slots (``dedup.Resolution``, one a
table; on the routed body the owner's, of the keys it received), and the
push that is handed it finds no key and reads no weight row again; a push
that is not runs as it did.
Tables fed one column of ids in one key form (:func:`plan_form`) share that
plan: it holds nothing of a store but the form (on the routed body, also
where a key goes), each store lays its own ownership mask over it
(``EmbeddingCollection.plan``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..analysis import scope
from ..ops import dedup
from ..optim.optimizers import SparseOptimizer, make_optimizer
from ..utils import observability
from . import alltoall as a2a
from . import hot_cache
from . import precision
from .mesh import DATA_AXIS, MODEL_AXIS


@dataclasses.dataclass(frozen=True, kw_only=True)
class PlaneSpec:
    """The fields the sharding specs of both kinds share and what derives
    from them; each spec adds its own fields and layout maths."""

    num_shards: int
    data_axis: str = DATA_AXIS
    model_axis: str = MODEL_AXIS
    plane: str = "a2a"   # "a2a" | "psum" | "a2a+cache" | "a2a+grouped"
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
    def routes(self) -> bool:
        """Pull and push ride the owner-routed exchange. A single shard
        has nothing to route: the masked-local body (whose collectives are
        free over size-1 axes) skips the bucketing machinery (~25% faster
        on one chip for the headline config), as the ``psum`` plane does
        by definition. The cached plane always routes: its residue masking
        composes with the exchange. A grouped-plane table addressed PER
        TABLE (serving probes, checkpoint paths) takes the plain a2a
        program — grouping only exists at the collection level."""
        return (self.plane != "psum" and self.num_shards > 1) \
            or self.is_cached

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
        return self.plane == "a2a+grouped"

    @property
    def shard_axes(self) -> tuple:
        """Mesh axes the table's row dimension is sharded over."""
        if self.plane != "psum":
            return (self.data_axis, self.model_axis)
        return (self.model_axis,)

    def row_spec(self) -> P:
        return P(self.shard_axes)


def _flat_keys(store, idx: jnp.ndarray, dim: int):
    """``idx`` as the flat key stream and the shape of its rows."""
    batch_shape = store.batch_shape(idx.shape)
    return (idx.reshape((-1,) + idx.shape[len(batch_shape):]),
            batch_shape + (dim,))


def _program_name(store, verb: str) -> str:
    # plane-identifiable HLO module name (jit names the module after the
    # callable): a contract-audit failure then says WHICH plane's
    # program regressed (analysis/contracts.py); compressed planes carry
    # their precision suffix (pull_a2a_bf16, ...)
    return f"{store.prefix}{verb}_{store.spec.plane_label.replace('+', '_')}"


def _exchange_args(mesh: Mesh, spec: PlaneSpec, batch_sharded: bool,
                   record_stats: bool) -> dict:
    """What ``a2a.exchange_pull`` and ``a2a.exchange_push`` are both told."""
    grid_axes, grid_sizes, split_axes, split_sizes = a2a.grid_info(
        mesh, spec.shard_axes, spec.model_axis, batch_sharded)
    return dict(num_shards=spec.num_shards, grid_axes=grid_axes,
                grid_sizes=grid_sizes, split_axes=split_axes,
                split_sizes=split_sizes, capacity=spec.a2a_capacity,
                slack=spec.a2a_slack, record_stats=record_stats)


def _my_shard(grid: dict) -> jnp.ndarray:
    return a2a.linear_shard_id(grid["grid_axes"], grid["grid_sizes"])


def shares_plan(spec: PlaneSpec, mesh: Mesh, batch_sharded: bool) -> bool:
    """A table's pull and push of one step can share one plan of its id
    column. On the masked-local body a :class:`dedup.Plan`, where a device
    pushes the keys it pulled (its push gathers no other device's slice of
    the batch). On the routed body an ``alltoall.RoutedPlan``, where pull
    and push route the same keys the same way: the plain exchange, not
    the cached plane (hits are masked out of the keys between the two),
    the grouped one (the collection's own exchange) or an ``int8_ef`` push
    (its residual is positional in the sender buffer it makes itself)."""
    if spec.routes:
        return not (spec.is_cached or spec.is_grouped or spec.is_int8_ef)
    return not batch_sharded or mesh.shape[spec.data_axis] == 1


def _require_shared(spec: PlaneSpec, mesh: Mesh, batch_sharded: bool):
    if not shares_plan(spec, mesh, batch_sharded):
        raise ValueError(
            f"plane {spec.plane_label!r} over {spec.num_shards} shard(s) "
            "has no plan: the masked-local body has one on a mesh with no "
            "data axis to gather the batch over, the routed body on the "
            "plain exchange (sharded.shares_plan)")


def _plan_specs(spec: PlaneSpec, batch_spec: P):
    """Of a step's plan, out of its program and into pull and push: the
    masked-local body's is every device's, the routed body's each
    device's own."""
    if not spec.routes:
        return dedup.Plan(uniq=P(), inverse=batch_spec, valid=P())
    own = P(spec.shard_axes)
    mine = dedup.Plan(uniq=own, inverse=own, valid=own, counts=own)
    return a2a.RoutedPlan(sender=mine, owners=own, dest=own, ok=own,
                          owner=mine, spilled=P())


def _resolved_spec(spec: PlaneSpec) -> P:
    """Of every leaf of a ``dedup.Resolution``: a shard's own, out of the
    pull and into the push."""
    return P(spec.shard_axes) if spec.routes else P(spec.model_axis)


@functools.lru_cache(maxsize=None)
def _plan_program(mesh: Mesh, store, batch_sharded: bool,
                  record_stats: bool = False):
    spec = store.spec
    batch_spec = P(spec.data_axis) if batch_sharded else P()

    if spec.routes:
        grid = _exchange_args(mesh, spec, batch_sharded, record_stats)

        def _plan(idx):
            flat, _ = _flat_keys(store, idx, 0)
            return a2a.plan_exchange(
                flat, store.owner, sentinel=store.sentinel(flat.dtype),
                **grid)
    else:
        def _plan(idx):
            flat, _ = _flat_keys(store, idx, 0)
            return dedup.plan_keys(flat, store.sentinel(flat.dtype))

    _plan.__name__ = _program_name(store, "plan")
    return jax.jit(shard_map(_plan, mesh=mesh, in_specs=(batch_spec,),
                             out_specs=_plan_specs(spec, batch_spec),
                             check_vma=False))


def plan_form(store, indices: jnp.ndarray) -> tuple:
    """All that :func:`plan_sharded` of ``indices`` takes from ``store``:
    the program's name, the axis the batch lies on, the shape of the key
    stream and the fill; on the routed body also where a key goes
    (``store.routing()``, the axes the table lies on) and the buckets'
    size. Two stores of one form give one column the same plan, bit for
    bit, so their tables can share it."""
    spec = store.spec
    routed = (store.routing(), spec.shard_axes, spec.a2a_capacity,
              spec.a2a_slack) if spec.routes else ()
    return (_program_name(store, "plan"), spec.data_axis,
            indices.shape, jnp.dtype(indices.dtype),
            store.batch_shape(indices.shape),
            store.sentinel(indices.dtype)) + routed


def plan_sharded(indices: jnp.ndarray, *, mesh: Mesh, store,
                 batch_sharded: bool = True):
    """The plan of ``indices`` for one step's :func:`pull_sharded` and
    :func:`apply_gradients_sharded` through ``store``'s table, where
    :func:`shares_plan` holds. On the masked-local body a
    :class:`dedup.Plan`: the keys as they come (no ownership mask: each
    store lays its own over the distinct keys), deduplicated at full
    capacity. On the routed body an ``alltoall.RoutedPlan``: each device's
    slice deduplicated, counted and bucketed by owner, the keys sent, and
    what each owner received deduplicated again, its counts summed. It
    serves every table whose store has this one's :func:`plan_form`."""
    _require_shared(store.spec, mesh, batch_sharded)
    record = store.spec.routes and observability.evaluate_performance()
    return _plan_program(mesh, store, batch_sharded, record)(indices)


@functools.lru_cache(maxsize=None)
def _pull_program(mesh: Mesh, store, dim: int, batch_sharded: bool,
                  record_stats: bool = False, planned: bool = False):
    """Cached jitted pull: eager callers (serving lookups, tests) would
    otherwise rebuild + retrace the shard_map closure every call."""
    spec = store.spec
    batch_spec = P(spec.data_axis) if batch_sharded else P()
    cache_specs = ()
    plan_specs = (_plan_specs(spec, batch_spec),) if planned else ()
    out_specs = (batch_spec, _resolved_spec(spec)) if planned else batch_spec

    if spec.routes:
        grid = _exchange_args(mesh, spec, batch_sharded, record_stats)

        def _pull_core(arrays, flat, me, plan=None):
            local = store.local(*arrays)
            # with the step's plan the owner reads a row a distinct key
            # it received (the masked-local body's read, over its own
            # plan) and returns what it resolved beside the rows
            return a2a.exchange_pull(
                flat, lambda keys: store.resolve(local, keys, me),
                store.owner, sentinel=store.sentinel(flat.dtype), dim=dim,
                wire_dtype=spec.pull_wire_dtype, plan=plan,
                read_plan=lambda mine: store.read_plan(
                    local, mine, record_stats, me), **grid)

        if spec.is_cached:
            cache_specs = (P(), P())        # replicated on every device

            def _pull(arrays, ckeys, crows, idx):
                flat, out_shape = _flat_keys(store, idx, dim)
                valid = store.valid(flat)
                pos, hit = hot_cache.lookup(ckeys, flat, valid)
                served = jnp.where(hit[:, None],
                                   jnp.take(crows, pos, axis=0),
                                   jnp.zeros((1, dim), crows.dtype))
                hot_cache.record_cache_stats(
                    hit, valid,
                    entry_bytes=dim * crows.dtype.itemsize + store.key_bytes,
                    split_axes=grid["split_axes"],
                    split_sizes=grid["split_sizes"], record=record_stats)
                resid = hot_cache.mask_hits(flat, hit,
                                            store.sentinel(flat.dtype))
                rows = _pull_core(arrays, resid, _my_shard(grid))
                return (rows + served).reshape(out_shape)
        else:
            def _pull(arrays, idx, *plan):
                me = _my_shard(grid)
                flat, out_shape = _flat_keys(store, idx, dim)
                out = _pull_core(arrays, flat, me, *plan)
                if not plan:
                    return out.reshape(out_shape)
                rows, resolved = out
                return rows.reshape(out_shape), resolved
    else:
        def _pull(arrays, idx, *plan):
            flat, out_shape = _flat_keys(store, idx, dim)
            local = store.local(*arrays)
            # with the step's plan a distinct key is read once: the psum
            # carries the distinct rows, and expand hands every position
            # its row, as exchange_pull's does
            resolved = store.read_plan(local, plan[0], record_stats) \
                if plan else None
            rows = scope.stage("exchange")(
                lambda rows: lax.psum(rows, spec.model_axis))(
                    resolved.rows if plan else store.read_local(local, flat))
            if not plan:
                return rows.reshape(out_shape)
            # the plan's capacity is its positions: no inverse is out
            # of range, and clip spares the fill mode's select
            rows = scope.stage("expand")(
                lambda rows, inverse: jnp.take(rows, inverse, axis=0,
                                               mode="clip"))(
                    rows, plan[0].inverse)
            # what this shard read, as it read it (a -0.0 weight has not
            # been through the sum), goes out for the step's push
            return rows.reshape(out_shape), resolved

    _pull.__name__ = _program_name(store, "pull")
    fn = shard_map(_pull, mesh=mesh,
                   in_specs=(store.specs(()),) + cache_specs + (batch_spec,)
                   + plan_specs,
                   out_specs=out_specs,
                   check_vma=False)
    return jax.jit(fn)


def _planned(plan, spec: PlaneSpec, mesh: Mesh, batch_sharded: bool,
             resolved=None) -> tuple:
    """``plan``, and the ``resolved`` that goes with it, as a program's
    trailing operands: none without a plan."""
    if plan is None:
        if resolved is not None:
            raise ValueError("a dedup.Resolution is of a plan's slots: "
                             "hand the push the plan its pull ran on")
        return ()
    _require_shared(spec, mesh, batch_sharded)
    return (plan,) if resolved is None else (plan, resolved)


def pull_sharded(state, indices: jnp.ndarray, *, mesh: Mesh, store,
                 batch_sharded: bool = True,
                 plan: Optional[dedup.Plan] = None) -> jnp.ndarray:
    """Distributed embedding lookup through ``store``'s table: the
    reference's pull RPC fan-out + response scatter
    (EmbeddingPullOperator.cpp:40-252).

    ``indices``: any shape (wide hash keys: a trailing pair axis), sharded
    over the data axis on dim 0 when ``batch_sharded`` (the normal training
    path) else replicated. Returns rows with the same batch sharding. On the
    ``"a2a+cache"`` plane ``state`` is a :class:`hot_cache.CachedState`.
    ``plan`` is :func:`plan_sharded`'s of the same ``indices``: the same
    rows, each distinct key resolved once (on the routed body: by its
    owner, of the keys round 1 brought it), and with them what was
    resolved, ``(rows, dedup.Resolution)``: the second for
    :func:`apply_gradients_sharded` of the same step, while nothing has
    written the table.
    """
    spec = store.spec
    record = observability.evaluate_performance()
    plan = _planned(plan, spec, mesh, batch_sharded)
    if spec.is_cached:
        table, cache = state.table, (state.cache.keys, state.cache.rows)
    else:
        # int8_ef states wrap the table with the push residual; pulls read
        # through the wrapper (serving restores may hand a bare table)
        table, cache = precision.unwrap(state), ()
    fn = _pull_program(mesh, store, table.weights.shape[-1], batch_sharded,
                       record, bool(plan))
    return observability.plane_timed(
        "pull", spec.plane_label, record, fn,
        store.operands(table.replace(slots={})), *cache, indices, *plan)


@functools.lru_cache(maxsize=None)
def _apply_program(mesh: Mesh, store, optimizer: SparseOptimizer, dim: int,
                   batch_sharded: bool, dedup_capacity: Optional[int],
                   slot_names: tuple, record_stats: bool = False,
                   planned: int = 0):
    spec = store.spec
    batch_spec = P(spec.data_axis) if batch_sharded else P()
    table_specs = store.specs(slot_names)
    extra_in = extra_out = ()
    plan_specs = (_plan_specs(spec, batch_spec),
                  _resolved_spec(spec))[:planned]

    if spec.routes:
        grid = _exchange_args(mesh, spec, batch_sharded, record_stats)

        def _push_core(arrays, flat, g2, ef=None, plan=None, resolved=None):
            local = store.local(*arrays)
            # Both branches of the push merge and write nothing: what they
            # return, one structure out of either, is written to the table
            # after them (``store.apply_merged``). With what the step's
            # pull resolved it holds each slot's weight row and a hash
            # key's slot; a step round 1 did not hold merges as it does
            # without a plan, and carries of those what it can without
            # the table.
            merge = functools.partial(
                store.merge, local, me=_my_shard(grid),
                dedup_capacity=dedup_capacity,
                carries=resolved is not None)

            out = a2a.exchange_push(
                flat, g2, merge, store.owner,
                sentinel=store.sentinel(flat.dtype),
                wire_dtype=spec.push_wire_dtype, ef_state=ef, plan=plan,
                # the owner merges by its plan of the keys round 1
                # brought it
                merge_plan=lambda grads: merge(
                    None, grads, None, plan=plan.owner, resolved=resolved),
                **grid)
            merged, new_ef = out if ef is not None else (out, ())
            return store.apply_merged(local, optimizer, merged,
                                      record_stats=record_stats) + (new_ef,)

        if spec.is_cached:
            cache_slot_specs = {name: P() for name in slot_names}
            extra_in = (P(), P(), cache_slot_specs)
            extra_out = (P(), cache_slot_specs)

            def _apply(arrays, ckeys, crows, cslots, idx, g):
                me = _my_shard(grid)
                flat, _ = _flat_keys(store, idx, dim)
                g2 = g.reshape(-1, dim)
                valid = store.valid(flat)
                pos, hit = hot_cache.lookup(ckeys, flat, valid)
                summed, counts = hot_cache.cache_pre_reduce(
                    pos, hit, g2, ckeys.shape[0], grid["split_axes"],
                    grid["split_sizes"], grid["grid_axes"])
                hot_cache.record_cache_stats(
                    hit, valid,
                    entry_bytes=dim * crows.dtype.itemsize
                    + store.key_bytes + 4,
                    split_axes=grid["split_axes"],
                    split_sizes=grid["split_sizes"], record=record_stats)
                # residue rides the exchange with hits masked invalid
                resid = hot_cache.mask_hits(flat, hit,
                                            store.sentinel(flat.dtype))
                carry, weights, slots, _ = _push_core(arrays, resid, g2)
                # identical psum'd totals on every device -> identical
                # replica update everywhere; the owner scatters its rows
                # back so the table stays authoritative
                cache = hot_cache.update_replica(
                    optimizer, hot_cache.HotCacheState(
                        keys=ckeys, rows=crows, slots=cslots),
                    summed, counts)
                # owner write-back: admitted keys are PRESENT in their
                # owner's shard; the scatter drops non-owned / untouched
                # rows
                slot = store.slot_of(carry, ckeys, me)
                sc = jnp.where((slot >= 0) & (counts > 0), slot,
                               weights.shape[0])
                weights = weights.at[sc].set(
                    cache.rows.astype(weights.dtype), mode="drop")
                slots = {name: slots[name].at[sc].set(
                    cache.slots[name].astype(slots[name].dtype),
                    mode="drop") for name in slots}
                return (store.outputs(carry, weights, slots,
                                      spec.shard_axes),
                        (cache.rows, cache.slots))
        else:
            if spec.is_int8_ef:
                # the EF residual buffers shard over the exchange grid:
                # each device owns exactly its sender slice's block
                extra_in = extra_out = (P(spec.shard_axes),) * 2

            def _apply(arrays, *rest):
                # an int8_ef push's residual comes in front of the batch,
                # a step's plan (never both) behind it
                at = len(rest) - planned
                *ef, idx, g = rest[:at]
                flat, _ = _flat_keys(store, idx, dim)
                carry, weights, slots, new_ef = _push_core(
                    arrays, flat, g.reshape(-1, dim), tuple(ef) or None,
                    *rest[at:])
                return (store.outputs(carry, weights, slots,
                                      spec.shard_axes), new_ef)
    else:
        def _apply(arrays, idx, g, *planned):
            plan, resolved = (planned + (None, None))[:2]
            flat, _ = _flat_keys(store, idx, dim)
            g2 = g.reshape(-1, dim)
            if batch_sharded:
                flat, g2 = scope.stage("exchange")(
                    lambda *xs: tuple(lax.all_gather(x, spec.data_axis,
                                                     tiled=True)
                                      for x in xs))(flat, g2)
            carry, weights, slots = store.apply_local(
                store.local(*arrays), optimizer, flat, g2,
                dedup_capacity=dedup_capacity, record_stats=record_stats,
                plan=plan, resolved=resolved)
            return store.outputs(carry, weights, slots, spec.model_axis), ()

    _apply.__name__ = _program_name(store, "push")
    fn = shard_map(_apply, mesh=mesh,
                   in_specs=(table_specs,) + extra_in
                   + (batch_spec, batch_spec) + plan_specs,
                   out_specs=(table_specs, extra_out),
                   check_vma=False)
    return jax.jit(fn)


def apply_gradients_sharded(state, optimizer: SparseOptimizer,
                            indices: jnp.ndarray, grads: jnp.ndarray, *,
                            mesh: Mesh, store, batch_sharded: bool = True,
                            dedup_capacity: Optional[int] = None,
                            plan: Optional[dedup.Plan] = None,
                            resolved: Optional[dedup.Resolution] = None):
    """Distributed push+update: every shard applies its owned rows.

    On the routed planes each key's pre-reduced grads reach its single
    owner shard; on the masked-local body data-axis devices all_gather the
    global (indices, grads) so the update is computed identically on every
    data replica of a model shard — replacing the reference's single-owner
    store RPC (WorkerContext.cpp:115-123) with deterministic replicated
    application. On the ``"a2a+cache"`` plane ``state`` is a
    :class:`hot_cache.CachedState`. ``plan`` is :func:`plan_sharded`'s of
    the same ``indices``, the one the step's pull ran on: its slots are the
    push's unique buffer, and the same rows get the same update.
    ``resolved`` is what that pull returned beside its rows, the table
    unwritten since: the push takes each key's slot from it and looks for
    none again.
    """
    spec = store.spec
    optimizer = make_optimizer(optimizer)
    record = observability.evaluate_performance()
    plan = _planned(plan, spec, mesh, batch_sharded, resolved)
    extra = ()
    if spec.is_cached:
        table = state.table
        extra = (state.cache.keys, state.cache.rows, state.cache.slots)
    elif spec.is_int8_ef and spec.num_shards > 1:
        # (a single shard has no wire: its push is the exact masked-local
        # program and the state stays a bare table)
        table = precision.unwrap(state)
        table, *extra = precision.ensure_ef(
            state, dim=table.weights.shape[-1],
            n_flat=int(np.prod(store.batch_shape(indices.shape))),
            data=mesh.shape[spec.data_axis],
            model=mesh.shape[spec.model_axis],
            batch_sharded=batch_sharded, **store.ef_space(table))
    else:
        table = precision.unwrap(state)
    fn = _apply_program(mesh, store, optimizer, table.weights.shape[-1],
                        batch_sharded, dedup_capacity, tuple(table.slots),
                        record, len(plan))
    outs, new_extra = observability.plane_timed(
        "push", spec.plane_label, record, fn,
        store.operands(table), *extra, indices, grads, *plan)
    table = store.rebuild(table, outs)
    if spec.is_cached:
        rows, slots = new_extra
        return hot_cache.CachedState(
            table=table, cache=hot_cache.HotCacheState(
                keys=state.cache.keys, rows=rows, slots=slots))
    if new_extra:
        keys, resid = new_extra
        return precision.EFState(table=table, keys=keys, resid=resid)
    return table

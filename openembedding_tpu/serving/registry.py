"""Serving-side model registry: load checkpoints, serve read-only lookups.

Capability parity with the reference's serving plane (SURVEY §3.5):

* ``ModelRegistry`` ≈ ModelManager + ModelController state
  (/root/reference/openembedding/client/ModelController.cpp): models are
  keyed by ``model_sign`` ("<uuid>-<version>", reference py_api.cc:130-138),
  carry CREATING/NORMAL/DELETING/ERROR status, loads run async (CREATING
  visible during load like the master-tree status), lookups against a
  CREATING/DELETING model are rejected (ModelController.cpp:24-44).
* ``ServingModel.lookup`` ≈ the read-only pull handler — no side effects:
  unknown hash keys return zero rows (EmbeddingPullOperator.cpp:179-181).
* Replicas: the reference replicates shards across PS nodes (replica_num=3
  default) and picks one per pull. One SPMD serving process holds exactly one
  copy of each table in HBM; HA is processes × load balancer, so
  ``replica_num`` here is metadata recorded for the deployment layer (each
  extra serving process IS a replica). Dead-process recovery = reload from
  the checkpoint URI, which ``load_model`` does from scratch — the
  restore-from-dump path of EmbeddingRestoreOperator.cpp:108-152.
"""

from __future__ import annotations

import contextlib
import threading
import traceback
from typing import Any, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..analysis import scope
from ..analysis.concurrency import make_lock, sync_point
from ..embedding import EmbeddingCollection, EmbeddingSpec
from ..meta import ModelMeta, ModelStatus, UNBOUNDED_VOCAB
from .. import checkpoint as ckpt_lib


class ServingModel:
    """One loaded model: collection + read-only states.

    ``shard_slice=(k, G)`` marks a SHARD-GROUP member: this process holds
    only ids/keys with ``id % G == k`` (the reference's shard placement
    over PS nodes, client/Model.cpp:153-186). Lookups accept GLOBAL ids:
    bounded ids are mapped to the local row space, non-owned ids return
    zero rows (the router only sends owned ids; stray ones are harmless).
    """

    def __init__(self, sign: str, collection: EmbeddingCollection,
                 states: Dict[str, Any], meta: ModelMeta,
                 shard_slice=None, version: int = 0):
        self.sign = sign
        self.collection = collection
        self.states = states
        self.meta = meta
        self.shard_slice = tuple(shard_slice) if shard_slice else None
        # hot-swap version: the delta-chain seq this model's states
        # reflect (checkpoint_delta.py). apply_delta bumps it together
        # with the states swap under the registry lock; readers snapshot
        # (states, version) in one reference grab, so a lookup is always
        # served from exactly one version
        self.version = int(version)
        # serializes CONCURRENT apply_delta builds for this model (the
        # build runs device programs; the registry lock only guards the
        # final publish)
        self.swap_lock = make_lock(f"serving.swap.{sign}")
        self._by_id = {collection.variable_id(name): name
                       for name in collection.specs}

    def variable_name(self, variable_id: int) -> str:
        return self._by_id[variable_id]

    def export_rows(self, variable: Any, offset: int, limit: int):
        """Page through this replica's live rows: ``(ids, rows, total)``.

        The peer-to-peer restore protocol (the reference's coordinated-
        restore iterator, server/EmbeddingRestoreOperator.cpp:12-106): a
        respawned replica pages ``offset`` from 0 to ``total`` on a LIVING
        peer and rebuilds state from the responses alone — no dump URI
        involved. Ids are GLOBAL (shard-sliced models re-globalize their
        local rows), so any group member can restore from any same-group
        peer.
        """
        name = (variable if isinstance(variable, str)
                else self._by_id[int(variable)])
        spec = self.collection.specs[name]
        from ..parallel import hot_cache
        state = hot_cache.unwrap(self.states[name])
        if spec.use_hash:
            total = int(state.keys.shape[0])
            hi = min(offset + limit, total)
            keys = np.asarray(jax.device_get(state.keys[offset:hi]))
            from .. import hash_table as hash_lib
            empty = hash_lib.empty_key(keys.dtype)
            if hash_lib.is_wide(keys):
                # wide (64-bit pair) keys: free iff the HI word is EMPTY;
                # ids travel as joined int64 (the wire is 64-bit anyway)
                live = keys[:, 1] != empty
                ids = hash_lib.join64(keys[live])
            else:
                live = keys != empty
                ids = keys[live].astype(np.int64)
            # weights are slot-parallel to keys: slice directly instead of
            # re-probing the table for slots already in hand (restore
            # wall-clock stays memcpy-bound, not probe-bound)
            rows = np.asarray(jax.device_get(
                state.weights[offset:hi]))[live] \
                if ids.size else np.zeros((0, spec.output_dim), np.float32)
            return ids, rows, total
        total = int(spec.input_dim)
        hi = min(offset + limit, total)
        local = np.arange(offset, hi, dtype=np.int64)
        if self.shard_slice is not None:
            k, G = self.shard_slice
            ids = local * G + k
        else:
            ids = local
        rows = np.asarray(self.lookup(name, ids)) \
            if ids.size else np.zeros((0, spec.output_dim), np.float32)
        return ids, rows, total

    def lookup(self, variable: Any, indices) -> jnp.ndarray:
        """Read-only pull for one variable (by name or variable_id).

        Shape contract (disambiguates by SEQUENCE AXIS, never by the
        pooled-spec training heuristic):

        - FLAT queries — narrow ``[n]`` ids or wide ``[n, 2]`` pairs —
          return ROW semantics: one row per id/pair, never pooled. This
          is what the routing planes assume (they merge rows back by
          position after fanning out flat lists,
          ha.ShardedRoutingClient.lookup); inferring "pairs" from a
          pooled spec's ndim>=3 rule here would misread the router's
          ``[n, 2]`` pair lists as ``[B, L=2]`` sequences and pool each
          32-bit word's row into garbage.
        - SEQUENCE queries on a pooled spec — narrow ``[B, L]`` or wide
          ``[B, L, 2]`` — return the training contract: pooled
          ``[B, dim]``.

        Carve-out: on a WIDE spec, ANY trailing dim of 2 is a pair axis
        — a genuine narrow length-2 sequence shaped ``[B, 2]`` would be
        misread as ``[B]`` (lo, hi) pairs. Pad such queries to L != 2
        with the spec's pad id, or send them as ``[B, L, 2]`` pairs.
        """
        name = (variable if isinstance(variable, str)
                else self._by_id[int(variable)])
        # ONE reference grab = one consistent version: a concurrent
        # apply_delta publishes a whole NEW states dict (never mutates
        # this one), so every row this lookup returns comes from exactly
        # one version — the swap-during-lookup interleaving schedule
        # pins this (tests/test_delta_checkpoint.py)
        states = self.states
        sync_point("serving.lookup.snapshot")
        return self._lookup_impl(name, indices, states)

    def batchable(self, variable: Any, indices) -> Optional[str]:
        """The variable NAME when this query can ride the micro-batcher
        (a FLAT row-semantics query: narrow ``[n]`` ids, or ``[n, 2]``
        pairs on a wide spec), else None. Sequence/pooled queries fall
        through to the direct path — batching concatenates key streams,
        which only preserves responses bit-identically for one-row-per-
        key semantics."""
        name = (variable if isinstance(variable, str)
                else self._by_id.get(int(variable)))
        if name is None or name not in self.collection.specs:
            return None
        spec = self.collection.specs[name]
        idx = np.asarray(indices)
        if not np.issubdtype(idx.dtype, np.integer):
            return None
        if idx.ndim == 1:
            return name
        # pair queries batch only in the router's wire form (int32
        # words): dedup_keys joins pairs via hash_table.join64, whose
        # uint32 word view rejects 64-bit-typed columns — those fall
        # through to the direct path, which widens them itself
        if idx.ndim == 2 and idx.shape[-1] == 2 and spec.use_hash \
                and spec.key_dtype == "wide" and idx.dtype == np.int32:
            return name
        return None

    def _lookup_impl(self, name: str, indices, states,
                     record: bool = True, span: bool = True) -> jnp.ndarray:
        """The pull against an EXPLICIT states snapshot — shared by the
        direct path (which snapshots per lookup) and the micro-batcher
        (ONE snapshot per flush covers every member request;
        ``record=False`` there — the batcher records per-REQUEST sizes
        at enqueue, so the deduped batch pull must not double-count).
        ``span=False`` suppresses the serving.lookup span: warm-up
        compiles must not land boot-time XLA compile latencies in the
        serving histograms."""
        spec = self.collection.specs[name]
        # serving-side batch stats: lookup-size histogram (always on)
        # + the gated uniqueness counters, through the same machinery
        # the training pull uses (record_batch_stats) — both land on
        # /metrics and in the graftscope distribution listing
        from ..utils import observability
        if record:
            observability.record_serving_lookup(
                name, getattr(indices, "size", None)
                or np.asarray(indices).size)
            if observability.evaluate_performance():
                observability.record_batch_stats(
                    {name: np.asarray(indices)})
        # narrow id columns address wide tables via the same widening
        # bridge the training pull uses; pair_ndim=2 so the serving wire's
        # flat pair lists always read as pairs. Widen BEFORE the device
        # conversion: host int64 ids are split on host, and with x64 off
        # jnp.asarray first would wrap them to int32 (another key's row)
        idx = jnp.asarray(self.collection._widen(spec, indices,
                                                 pair_ndim=2))
        seq_ndim = 3 if spec.use_hash and spec.key_dtype == "wide" else 2
        as_rows = spec.pooling is None or idx.ndim < seq_ndim
        if self.shard_slice is not None:
            # owner rule: id % G on the (joined) 64-bit value — must match
            # the loader's slice filter (checkpoint._insert_hash_rows) and
            # the router's partition (ha.ShardedRoutingClient.lookup)
            k, G = self.shard_slice
            if not spec.use_hash:
                idx = jnp.where(idx % G == k, idx // G, -1)
            elif spec.key_dtype == "wide":
                from .. import hash_table as hash_lib
                # [.., 2] pairs: owner on the JOINED value, non-owned pairs
                # masked WHOLE (an elementwise % would test the lo and hi
                # words independently — corrupting pairs)
                if idx.ndim < 2 or idx.shape[-1] != 2:
                    raise ValueError(
                        f"variable {name!r} takes [..., 2] int32 pair "
                        f"queries (hash_table.split64), got shape "
                        f"{idx.shape}")
                empty = hash_lib.empty_key(jnp.int32)
                owned = hash_lib.pair_mod(idx, G) == k
                idx = jnp.where(owned[..., None], idx, empty)
            else:
                from .. import hash_table as hash_lib
                empty = hash_lib.empty_key(idx.dtype)
                idx = jnp.where(idx % G == k, idx, empty)
        ctx = (scope.span("serving.lookup", table=name) if span
               else contextlib.nullcontext())
        with ctx:
            rows = self.collection.pull(states, {name: idx},
                                        batch_sharded=False,
                                        read_only=True,
                                        serving_rows=as_rows)
        return rows[name]


def _specs_from_meta(meta: ModelMeta, hash_capacity: int,
                     num_shards: int = -1,
                     shard_slice=None) -> List[EmbeddingSpec]:
    """Rebuild EmbeddingSpecs from a checkpoint's model_meta — the serving
    process needs no model code, just the dump (like TF-Serving + the
    reference's SavedModel + <dir>/openembedding sidecar). Hash geometry
    (capacity/key dtype) comes from the meta's ``hash_variables`` extra when
    the checkpoint recorded it, so serving tables can hold every trained row."""
    from .. import checkpoint as ckpt_mod
    hash_info = meta.extra.get("hash_variables", {})
    poolings = meta.extra.get("variable_pooling", {})
    specs = []
    for v in sorted(meta.variables, key=lambda v: v.variable_id):
        hash_var = v.meta.vocabulary_size >= UNBOUNDED_VOCAB
        info = hash_info.get(v.name, {})
        vocab = v.meta.vocabulary_size
        cap = int(info.get("hash_capacity", hash_capacity))
        if shard_slice is not None:
            # shard-group member: bounded vocab shrinks to the owned rows,
            # hash capacity to this shard's share
            k, G = shard_slice
            vocab = ckpt_mod.shard_slice_vocab(vocab, k, G)
            cap = max(1, -(-cap // G))
        specs.append(EmbeddingSpec(
            name=v.name, input_dim=-1 if hash_var else vocab,
            output_dim=v.meta.embedding_dim, dtype=v.meta.datatype,
            # serving is read-only: the stateless "default" optimizer means
            # no slot arrays are allocated or loaded (the reference serves
            # through the no-optimizer default, EmbeddingOptimizer.h default)
            optimizer={"category": "default"},
            hash_capacity=cap,
            key_dtype=info.get("key_dtype", "int32"),
            num_shards=num_shards,
            pooling=poolings.get(v.name)))
    return specs


class ModelRegistry:
    """All models served by this process, with lifecycle management."""

    def __init__(self, mesh, *, default_hash_capacity: int = 2**20):
        self.mesh = mesh
        self.default_hash_capacity = default_hash_capacity
        # make_lock: plain Lock unless OE_REPORT_TRACE_LOCKS enables the
        # graftrace runtime detector (analysis/concurrency.py)
        self._lock = make_lock("serving.registry")
        self._models: Dict[str, ServingModel] = {}
        self._status: Dict[str, Dict[str, Any]] = {}
        # outstanding async create_model load threads, by sign; joined
        # by close() so shutdown quiesces instead of relying on daemon
        # teardown killing a loader mid-commit
        self._loaders: Dict[str, threading.Thread] = {}
        # micro-batching (serving/batcher.py): enable_batching arms the
        # config; per-model batchers are created lazily on first batched
        # lookup and drained at delete/close
        self._batch_cfg: Optional[Dict[str, Any]] = None
        self._batchers: Dict[str, Any] = {}
        # graftplan online mode: PlanConfig envelope; when its kill
        # switch (plan.online) is armed, each lazily-created batcher
        # gets an AdaptiveBatchTuner, stopped at drain time
        self._batch_plan: Optional[Any] = None
        self._tuners: Dict[str, Any] = {}
        from ..utils import observability
        observability.register_memory_source("serving", "registry", self)

    def memory_stats(self) -> Dict[str, float]:
        """Loaded-model memory gauges (``observability.memory_stats``):
        NORMAL-status model count and the summed byte size of their
        state leaves (tables + hash keys; read-only serving carries no
        optimizer slots)."""
        import jax as _jax
        with self._lock:
            models = list(self._models.values())
        total = 0
        for m in models:
            total += sum(int(x.nbytes)
                         for x in _jax.tree.leaves(m.states))
        return {"loaded_models": float(len(models)),
                "model_bytes": float(total)}

    # --- lifecycle (ModelController.create/delete/show equivalents) -------
    def create_model(self, model_uri: str, *, model_sign: Optional[str] = None,
                     replica_num: int = 3, num_shards: int = -1,
                     shard_index: int = 0, shard_count: int = 1,
                     block: bool = True) -> str:
        """Load a checkpoint for serving; returns the model_sign.

        Async when ``block=False``: status is CREATING until the load thread
        finishes (reference ModelController.cpp:47-85 thread-group load).
        ``shard_count > 1`` loads only this process's shard slice (ids/keys
        ≡ shard_index mod shard_count) so a model larger than one process
        serves from a shard group — the reference's shard x replica
        placement over PS nodes (client/Model.cpp:153-186).
        """
        from ..utils import fs as fs_lib
        with fs_lib.open_file(
                fs_lib.join(model_uri, ckpt_lib.MODEL_META_FILE), "rb") as f:
            meta = ModelMeta.loads(f.read().decode("utf-8"))
        sign = model_sign or meta.model_sign or model_uri
        shard_slice = (shard_index, shard_count) if shard_count > 1 else None
        with self._lock:
            if sign in self._status and \
                    self._status[sign]["model_status"] == ModelStatus.CREATING:
                raise ValueError(f"model {sign!r} is already being created")
            self._status[sign] = {
                "model_sign": sign, "model_uri": model_uri,
                "model_status": ModelStatus.CREATING, "model_error": "",
                "replica_num": replica_num,
                "shard_index": shard_index, "shard_count": shard_count,
            }

        def _load():
            try:
                sync_point("registry.load.start")
                with scope.span("registry.load", detail={"sign": sign}):
                    specs = _specs_from_meta(meta,
                                             self.default_hash_capacity,
                                             num_shards, shard_slice)
                    coll = EmbeddingCollection(specs, self.mesh)
                    # hot-swap version = the delta-chain seq THIS load
                    # replayed (0 for plain full checkpoints), reported
                    # by the load itself. A separate applied_seq() read
                    # here could see a delta committed AFTER the replay
                    # — the model would then claim a version whose rows
                    # it does not hold and ack that delta's push as
                    # stale, silently losing it (graftproto-found
                    # divergence, pinned in test_graftproto_replay.py)
                    load_info: Dict[str, Any] = {}
                    states = ckpt_lib.load_checkpoint(
                        model_uri, coll, shard_slice=shard_slice,
                        info=load_info)
                    model = ServingModel(
                        sign, coll, states, meta,
                        shard_slice=shard_slice,
                        version=int(load_info.get("applied_seq", 0)))
                sync_point("registry.load.commit")
                with self._lock:
                    self._models[sign] = model
                    self._status[sign]["model_status"] = ModelStatus.NORMAL
                    self._status[sign]["version"] = model.version
                # a same-sign RELOAD replaced the model object: drain
                # the replaced model's batcher so its closures stop
                # pinning the old states (_batcher_for also refuses to
                # hand out a batcher bound to a replaced model, so this
                # is resource hygiene, not correctness). keep_model
                # spares a batcher a racing lookup already bound to
                # the NEW model.
                self._close_batchers([sign], keep_model=model)
            except Exception as e:  # noqa: BLE001 — recorded, not swallowed
                with self._lock:
                    self._status[sign]["model_status"] = ModelStatus.ERROR
                    self._status[sign]["model_error"] = (
                        f"{e}\n{traceback.format_exc()}")
            finally:
                # self-prune so a long-lived server's churn of async
                # creates does not accumulate dead Thread objects until
                # close. IDENTITY-guarded: after a failed load a retry
                # may already have registered a NEW loader under this
                # sign — popping that one would leave it untracked by
                # close() (no-op for the block=True caller and when
                # join_loads already swapped the dict out)
                me = threading.current_thread()
                with self._lock:
                    if self._loaders.get(sign) is me:
                        del self._loaders[sign]

        if block:
            _load()
            with self._lock:
                err = dict(self._status[sign])
            if err["model_status"] == ModelStatus.ERROR:
                raise RuntimeError(err["model_error"])
        else:
            t = threading.Thread(target=_load, daemon=True,
                                 name=f"oe-model-load-{sign}")
            # publish + start under ONE lock hold: a concurrent close()
            # between the two would join a never-started thread (raises)
            with self._lock:
                self._loaders[sign] = t
                t.start()
        return sign

    # --- micro-batched lookups (serving/batcher.py) ------------------------
    def enable_batching(self, *, max_batch_rows: int = 0,
                        max_wait_us: Optional[int] = None,
                        max_queue_rows: int = 0,
                        timeout: float = 30.0,
                        plan: Optional[Any] = None) -> None:
        """Arm the micro-batching lookup scheduler: concurrent flat
        lookups against one model coalesce into ONE key-deduped batched
        pull per flush (``serving/batcher.py``; zero/None keeps the
        batcher default — an EXPLICIT ``max_wait_us=0`` is honored:
        flush immediately, coalescing only what is already queued).
        Responses stay bit-identical to unbatched lookups — each flush
        snapshots exactly one model version (graftproto
        ``serving_batcher``). Call before serving traffic; the REST
        plane routes through :meth:`lookup` automatically.

        ``plan`` (an ``envconfig.PlanConfig``) arms graftplan's ONLINE
        mode when its ``online`` kill switch is set: every batcher gets
        an :class:`batcher.AdaptiveBatchTuner` moving max_batch_rows /
        max_wait_us inside the plan's floor/ceiling envelope.
        """
        from . import batcher as batcher_mod
        # fallbacks resolve through the LIVE knob accessor, never an
        # import-time snapshot of the envconfig constants (the online
        # tuner and test monkeypatches both rely on late reads)
        defaults = batcher_mod.knob_defaults()
        cfg = {"max_batch_rows": max_batch_rows
               or defaults["max_batch_rows"],
               "max_wait_us": defaults["max_wait_us"]
               if max_wait_us is None else max_wait_us,
               "max_queue_rows": max_queue_rows
               or defaults["max_queue_rows"],
               "timeout": timeout}
        with self._lock:
            self._batch_cfg = cfg
            self._batch_plan = plan

    @property
    def batching_enabled(self) -> bool:
        with self._lock:
            return self._batch_cfg is not None

    def _batcher_for(self, sign: str, model: ServingModel):
        """This sign's batcher, created lazily under the registry lock
        and bound to ONE ServingModel object (the flusher thread starts
        at construction; pulls read the model's PUBLISHED state
        reference once per flush, so apply_delta hot-swaps keep working
        untouched — but a same-sign model REPLACEMENT via
        create_model/register_model gets a fresh batcher, the stale one
        drained: its closures capture the replaced model and would
        serve the old checkpoint's rows forever)."""
        from . import batcher as batcher_mod
        stale = None
        stale_tuner = None
        try:
            with self._lock:
                entry = self._batchers.get(sign)
                if entry is not None:
                    if entry[0] is model:
                        return entry[1]
                    stale = self._batchers.pop(sign)[1]
                    stale_tuner = self._tuners.pop(sign, None)
                # only LIVE models get a (re)created batcher: a lookup
                # racing delete_model must not resurrect a flusher
                # thread for the deleted sign (it would pin the dead
                # model's states until close())
                if self._batch_cfg is None \
                        or self._models.get(sign) is not model:
                    return None
                b = self._make_batcher(sign, model, self._batch_cfg)
                self._batchers[sign] = (model, b)
                if self._batch_plan is not None \
                        and getattr(self._batch_plan, "online", False):
                    self._tuners[sign] = batcher_mod.AdaptiveBatchTuner(
                        b, self._batch_plan)
                return b
        finally:
            if stale_tuner is not None:
                stale_tuner.stop(restore=False)
            if stale is not None:
                # outside the registry lock: the drain flush pulls
                # against the old model's snapshot (device work)
                stale.close()

    def _make_batcher(self, sign: str, model: ServingModel, cfg):
        from . import batcher as batcher_mod

        def _snap(model=model):
            # the flush's one reference grab — the same discipline
            # ServingModel.lookup pins per single lookup
            return model.states

        def _pull(states, name, uniq, model=model):
            # BUCKET the unique count to powers of two before the
            # jitted pull: every distinct shape is its own XLA
            # compile, and raw dedup counts vary per flush — the
            # first measured storm spent its whole window compiling
            # hundreds of one-off programs. Padding repeats the
            # last key (a read-only gather makes duplicates free)
            # and the pad rows are sliced off before the scatter.
            n = int(uniq.shape[0])
            if n:
                # floor 64: small flushes share one shape; see
                # warm_batch_programs for the boot-time compile
                cap = 1 << max(6, (n - 1).bit_length())
                if cap != n:
                    uniq = np.concatenate(
                        [uniq, np.repeat(uniq[-1:], cap - n, axis=0)])
            rows = np.asarray(model._lookup_impl(
                name, uniq, states, record=False), np.float32)
            return rows[:n]

        return batcher_mod.LookupBatcher(sign, _snap, _pull, **cfg)

    def warm_batch_programs(self, *, dtypes=("int32", "int64")) -> int:
        """Pre-compile the batched pull programs every NORMAL model's
        flushes will dispatch (each power-of-two bucket x key dtype is
        one XLA program): a serving daemon warms at boot so the first
        storm measures STEADY-state latency, not compile stalls.
        Returns the number of programs warmed. No-op unless batching
        is armed."""
        with self._lock:
            cfg = self._batch_cfg
            plan = self._batch_plan
            models = list(self._models.values())
        if cfg is None:
            return 0
        # online mode warms to the adaptive CEILING, not the configured
        # static cap: the tuner may grow max_batch_rows mid-storm and a
        # cold XLA compile in the serving path would eat the win
        warm_rows = cfg["max_batch_rows"]
        if plan is not None and getattr(plan, "online", False):
            warm_rows = max(warm_rows, plan.rows_ceiling)
        n = 0
        for model in models:
            states = model.states
            for name, spec in model.collection.specs.items():
                wide = spec.use_hash and spec.key_dtype == "wide"
                cap = 64
                while True:
                    # wide tables serve BOTH int32 pair queries and
                    # narrow joined-id queries (the widening bridge),
                    # and batchable routes both to the batcher — warm
                    # every program the flushes can dispatch
                    for dt in dtypes:
                        model._lookup_impl(name,
                                           np.zeros(cap, np.dtype(dt)),
                                           states, record=False,
                                           span=False)
                        n += 1
                    if wide:
                        model._lookup_impl(name,
                                           np.zeros((cap, 2), np.int32),
                                           states, record=False,
                                           span=False)
                        n += 1
                    if cap >= warm_rows:
                        break
                    cap <<= 1
        return n

    def lookup(self, sign: str, variable: Any, indices) -> np.ndarray:
        """Serve one lookup, micro-batched when armed and the query is
        flat (row semantics); sequence/pooled queries and disabled
        batching fall through to the direct ``ServingModel.lookup``.
        Raises ``batcher.BusyError`` when the bounded queue rejects the
        offer (REST maps it to 429-busy)."""
        model = self.find_model(sign)
        idx = np.asarray(indices)
        with self._lock:
            cfg = self._batch_cfg
        name = model.batchable(variable, idx) if cfg is not None else None
        if name is not None:
            b = self._batcher_for(sign, model)
            # oversized single requests bypass the batcher: they would
            # flush alone into a pow2 bucket ABOVE the warmed ladder
            # (an un-warmed XLA compile in the serving path); the
            # direct pull compiles per raw shape exactly as the
            # unbatched plane always has, so they are no worse off
            # there. The cap is the batcher's LIVE knob (one attribute
            # read — the online tuner moves it mid-storm), never the
            # armed-time config snapshot.
            if b is not None and int(idx.shape[0]) <= b.max_batch_rows:
                return b.lookup(name, idx)
            # batching disarmed/closed between the check and the
            # batcher fetch (registry.close racing a request): the
            # direct path below stays correct
        return model.lookup(variable, idx)

    def _close_batchers(self, signs=None, keep_model=None) -> None:
        """Drain + drop batchers. ``keep_model`` protects a batcher
        already bound to that model object: a reload's post-publish
        cleanup must not close the fresh batcher a concurrent lookup
        just created for the NEW model (it would answer live requests
        with spurious busy rejections)."""
        with self._lock:
            if signs is None:
                entries, self._batchers = list(self._batchers.values()), {}
                tuners, self._tuners = list(self._tuners.values()), {}
            else:
                entries = []
                tuners = []
                for s in signs:
                    entry = self._batchers.get(s)
                    if entry is None or entry[0] is keep_model:
                        continue
                    entries.append(self._batchers.pop(s))
                    t = self._tuners.pop(s, None)
                    if t is not None:
                        tuners.append(t)
        for t in tuners:
            # before the drain: no knob step may land on a closing
            # batcher (restore is pointless — the batcher is going away)
            t.stop(restore=False)
        for _model, b in entries:
            # outside the registry lock: close() drains the queue, and
            # a drain flush pulls against the model (device work)
            b.close()

    def join_loads(self, timeout: float = 60.0) -> None:
        """Wait for every outstanding async ``create_model`` load thread
        (per-thread ``timeout`` seconds; a stuck loader is abandoned, not
        waited on forever — its status stays CREATING and the next
        create_model for that sign still raises)."""
        with self._lock:
            loaders, self._loaders = dict(self._loaders), {}
        for t in loaders.values():
            t.join(timeout)

    def close(self, timeout: float = 60.0) -> None:
        """Quiesce the registry: join async loaders so shutdown never
        relies on daemon teardown killing one mid-commit, and drain
        every model's micro-batcher (accepted requests get their
        response; later offers reject as busy). Batching disarms so a
        straggler lookup cannot resurrect a flusher thread after the
        quiesce."""
        self.join_loads(timeout)
        with self._lock:
            self._batch_cfg = None
            self._batch_plan = None
        self._close_batchers()

    def register_model(self, model: ServingModel, *,
                       replica_num: int = 3) -> str:
        """Install an externally assembled model (peer-to-peer restore:
        the states were streamed from a living replica, not a dump)."""
        ss = model.shard_slice or (0, 1)
        with self._lock:
            self._models[model.sign] = model
            self._status[model.sign] = {
                "model_sign": model.sign,
                "model_uri": model.meta.model_uri or "",
                "model_status": ModelStatus.NORMAL, "model_error": "",
                "replica_num": replica_num,
                "shard_index": ss[0], "shard_count": ss[1],
                "version": model.version,
            }
        # drain any batcher bound to a model this install replaced
        # (same hygiene as the create_model reload path)
        self._close_batchers([model.sign], keep_model=model)
        return model.sign

    def apply_delta(self, sign: str, delta) -> Dict[str, Any]:
        """Streaming hot-swap: patch a loaded model's rows in place from
        a trainer-published delta (``checkpoint_delta.Delta`` or its
        ``encode_delta`` wire bytes) — live model updates every N steps
        WITHOUT a full-model reload, the train->serve loop the reference
        closes with TF-Serving + the HA PS.

        Version-gated: the delta's ``seq`` must be exactly
        ``model.version + 1`` (deltas are incremental; a gap would lose
        the skipped delta's rows — catch up via
        ``checkpoint_delta.read_deltas_since`` or reload). A stale seq
        is acknowledged as a no-op (replays from a retrying publisher
        are idempotent). The patched states are built FUNCTIONALLY
        (non-donating scatter/insert) and published as one reference
        swap under the registry lock, so in-flight lookups keep their
        snapshot and new lookups see the new version whole — readers
        never observe a mixed version.
        """
        from .. import checkpoint_delta as cd
        from ..utils import observability
        if isinstance(delta, (bytes, bytearray)):
            delta = cd.decode_delta(bytes(delta))
        model = self.find_model(sign)
        with model.swap_lock:
            if delta.seq <= model.version:
                return {"applied": False, "version": model.version,
                        "reason": f"stale delta seq {delta.seq}"}
            if delta.seq != model.version + 1:
                raise RuntimeError(
                    f"model {sign!r} is at version {model.version}; "
                    f"delta seq {delta.seq} leaves a gap — apply the "
                    "chain in order (read_deltas_since) or reload")
            sync_point("registry.swap.build")
            with scope.span("registry.apply_delta",
                            detail={"sign": sign, "seq": delta.seq}):
                new_states = cd.apply_delta_to_states(
                    model.collection, model.states, delta.vars,
                    shard_slice=model.shard_slice,
                    with_opt=False, donate=False)
                # surface apply errors HERE, not under a later reader
                import jax as _jax
                _jax.block_until_ready(_jax.tree.leaves(new_states))
            sync_point("registry.swap.commit")
            with self._lock:
                model.states = new_states
                model.version = int(delta.seq)
                if sign in self._status:
                    self._status[sign]["version"] = model.version
        observability.record_swap(delta.rows, delta.seq)
        return {"applied": True, "version": int(delta.seq),
                "rows": int(delta.rows)}

    def delete_model(self, sign: str) -> None:
        with self._lock:
            if sign not in self._status:
                raise KeyError(sign)
            self._status[sign]["model_status"] = ModelStatus.DELETING
            self._models.pop(sign, None)
            del self._status[sign]
        # drain this model's batcher AFTER the status flip: in-flight
        # flushes finish against their snapshot, new offers reject
        self._close_batchers([sign])

    def find_model(self, sign: str) -> ServingModel:
        """NORMAL-status model or error — the find_model_variable gate
        (ModelController.cpp:24-44 rejects CREATING)."""
        sync_point("registry.find")
        with self._lock:
            st = self._status.get(sign)
            if st is None:
                raise KeyError(f"unknown model {sign!r}")
            if st["model_status"] != ModelStatus.NORMAL:
                raise RuntimeError(
                    f"model {sign!r} is {st['model_status']}: "
                    f"{st.get('model_error', '')}")
            return self._models[sign]

    def show_model(self, sign: str) -> Dict[str, Any]:
        with self._lock:
            if sign not in self._status:
                raise KeyError(sign)
            return dict(self._status[sign])

    def show_models(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(v) for v in self._status.values()]

    # --- nodes (show_node/shutdown_node analogues over jax devices) --------
    def show_nodes(self) -> List[Dict[str, Any]]:
        import jax
        return [{"node_id": d.id, "platform": d.platform,
                 "kind": getattr(d, "device_kind", "")}
                for d in self.mesh.devices.flatten()]

"""High-level embedding API: specs + a collection of sharded variables.

This is the TPU-native counterpart of the reference's Python surface
(/root/reference/openembedding/tensorflow/exb.py):

* ``EmbeddingSpec`` ≈ ``embed.Embedding(...)`` constructor arguments
  (exb.py:388-443): ``input_dim=-1`` selects the unbounded hash-key space
  (exb.py:231-233 maps it to vocab 2^63), per-variable optimizer/initializer
  configs use the same string-dict convention (exb.py:25-86).
* ``EmbeddingCollection`` ≈ the Context + per-layer ``Variable`` machinery
  (exb.py:222-360): it assigns variable ids by registration order
  (WorkerContext.cpp:95-113), owns each variable's sharding layout over the
  mesh, and exposes the three data-plane verbs —

  - ``init(rng)``            ≈ create_storage + create_variable + initializer
  - ``pull(states, inputs)``  ≈ ``sparse_read`` → PullWeights for every layer
  - ``apply_gradients(states, inputs, row_grads)`` ≈ PushGradients +
    UpdateWeights for the whole model in one fused program. The reference's
    fake-gradient allreduce barrier (exb_ops.cpp:434-437) has no equivalent
    because the SPMD step is already synchronous.

The dense half of a model (MLPs, small `sparse_as_dense` embeddings —
exb.py:100-104) lives in ordinary flax params, replicated and data-parallel,
exactly like the reference keeps small embeddings as plain tf.Variables under
Horovod allreduce.

Everything is functional: states are pytrees, the collection itself is static
configuration (hashable, safe to close over in jit).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .meta import (EmbeddingVariableMeta, ModelMeta, ModelVariableMeta,
                   UNBOUNDED_VOCAB)
from .optim.initializers import make_initializer
from .optim.optimizers import make_optimizer
from . import table as table_lib
from .parallel import sharded
from .parallel import sharded_table as st
from .parallel import sharded_hash as sh
from .parallel.mesh import MODEL_AXIS
from .utils import observability
from . import ragged


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class SameColumns:
    """Which input columns of one step hold the same ids as another:
    ``(twin, base)`` names, as :meth:`EmbeddingCollection.same_columns`
    observed them in the host batch. A pytree node without leaves: a
    jitted step that takes it with its batch is cached a value, so that
    the program can read both names from ONE traced column (two parameters
    of equal value are two columns to it) and :meth:`EmbeddingCollection.
    plan` can build the pair one ``dedup.Plan``."""

    twins: Tuple[Tuple[str, str], ...] = ()

    def bind(self, inputs: Dict[str, Any]) -> Dict[str, Any]:
        """``inputs`` with every twin reading its base's column."""
        out = dict(inputs)
        for twin, base in self.twins:
            out[twin] = out[base]
        return out


@dataclasses.dataclass(frozen=True)
class EmbeddingSpec:
    """Static description of one embedding variable (one reference Embedding
    layer, exb.py:388-420)."""

    name: str
    input_dim: int                   # -1 => unbounded hash-key space
    output_dim: int
    dtype: str = "float32"
    optimizer: Any = None            # None => collection default
    initializer: Any = None          # None => collection default
    num_shards: int = -1             # -1 => one shard per device (a2a plane)
    hash_capacity: int = 2**20       # reserve_items for hash variables
    layout: str = "mod"              # array-table row layout
    key_dtype: Optional[str] = None  # hash key storage; None resolves to
                                     # "wide" for hash variables — [.., 2]
                                     # int32 (lo, hi) pairs = the full
                                     # 64-bit space with x64 OFF (the
                                     # reference's default 2^63 key space,
                                     # Meta.h:44-46; pair queries via
                                     # hash_table.split64, plain int32/
                                     # int64 id columns widened on device).
                                     # "int32" is the explicit optimization
                                     # for small key spaces; "int64" needs
                                     # the global x64 flag
    plane: str = "a2a"               # "a2a" owner-routed | "psum" baseline
                                     # | "a2a+cache" (a2a + hot-row replica,
                                     # parallel/hot_cache.py)
                                     # | "a2a+grouped" (collection batches
                                     # same-shape tables into ONE exchange
                                     # per group per step,
                                     # parallel/grouped.py)
    a2a_capacity: int = 0            # per-destination bucket rows; 0 = auto
    a2a_slack: float = 2.0           # auto bucket = slack * mean
    cache_k: int = 0                 # hot-row replica slots; 0 = default
    exchange_precision: str = "f32"  # pulled rows on the wire: f32 | bf16
                                     # (parallel/precision.py; a "+bf16"/
                                     # "+int8" plane suffix is shorthand)
    push_precision: str = "f32"      # pre-reduced grads on the wire:
                                     # f32 | bf16 | int8_ef (per-row-scale
                                     # int8 with an error-feedback
                                     # residual in the state pytree)
    cache_refresh_every: int = 64    # admission refresh period (steps)
    cache_decay: float = 0.8         # frequency-sketch decay per refresh
    pooling: Optional[str] = None    # sequence combiner: sum | mean | sqrtn;
                                     # inputs become [B, L] padded id matrices
                                     # (ragged.py; reference RaggedTensor
                                     # lookups, exb.py:315-321)

    def __post_init__(self):
        if self.key_dtype is None:
            # out-of-box hash variables hold the reference's full hashed
            # key space (2^62 ids) — int32 (2^31 ids) is opt-in
            object.__setattr__(self, "key_dtype",
                               "wide" if self.input_dim == -1 else "int32")
        # a "+bf16"/"+int8" plane suffix is shorthand for the
        # compressed-exchange rungs: normalize it into the precision
        # fields so spec.plane always names the BASE data plane
        # (parallel/precision.py; conflicts and illegal combinations
        # raise in st._resolve_precision)
        base, ep, pp = st._resolve_precision(
            self.plane, self.exchange_precision, self.push_precision)
        object.__setattr__(self, "plane", base)
        object.__setattr__(self, "exchange_precision", ep)
        object.__setattr__(self, "push_precision", pp)

    @property
    def use_hash(self) -> bool:
        return self.input_dim == -1

    def meta(self) -> EmbeddingVariableMeta:
        vocab = UNBOUNDED_VOCAB if self.use_hash else self.input_dim
        return EmbeddingVariableMeta(datatype=self.dtype,
                                     embedding_dim=self.output_dim,
                                     vocabulary_size=vocab)


class EmbeddingCollection:
    """All sparse variables of one model, sharded over one mesh.

    ``states`` (returned by :meth:`init`, threaded through ``pull`` /
    ``apply_gradients``) is a plain dict ``name -> TableState|HashTableState``
    — a pytree suitable for jit donation and checkpointing.
    """

    def __init__(self, specs, mesh: Mesh,
                 default_optimizer: Any = None,
                 default_initializer: Any = None):
        if default_optimizer is None:
            default_optimizer = {"category": "sgd", "learning_rate": 0.01}
        if default_initializer is None:
            default_initializer = dict(table_lib.DEFAULT_INITIALIZER)
        self.mesh = mesh
        self.specs: Dict[str, EmbeddingSpec] = {}
        # chunk-level dirty bitmaps for delta checkpoints (dirty.py);
        # empty until enable_dirty_tracking() — marking is then fed by
        # the Trainer's host loop and by eager apply_gradients calls
        self._dirty_trackers: Dict[str, Any] = {}
        self._variable_ids: Dict[str, int] = {}
        self._optimizers = {}
        self._initializers = {}
        self._shardings = {}
        # what parallel/sharded.py's builder asks of each table's kind;
        # the serving (read_only) contract is a store of its own
        self._stores = {}
        self._serving_stores = {}
        for i, spec in enumerate(specs):
            if spec.name in self.specs:
                raise ValueError(f"duplicate embedding name {spec.name!r}")
            if spec.pooling is not None and spec.pooling not in ragged.POOLINGS:
                raise ValueError(
                    f"embedding {spec.name!r}: unknown pooling "
                    f"{spec.pooling!r}; known: {ragged.POOLINGS}")
            self.specs[spec.name] = spec
            self._variable_ids[spec.name] = i
            self._optimizers[spec.name] = make_optimizer(
                spec.optimizer if spec.optimizer is not None else default_optimizer)
            self._initializers[spec.name] = make_initializer(
                spec.initializer if spec.initializer is not None else default_initializer)
            if spec.use_hash:
                self._shardings[spec.name] = sh.make_hash_sharding_spec(
                    mesh, total_capacity=spec.hash_capacity,
                    num_shards=spec.num_shards, plane=spec.plane,
                    a2a_capacity=spec.a2a_capacity, a2a_slack=spec.a2a_slack,
                    key_width=64 if spec.key_dtype == "wide" else 32,
                    cache_k=spec.cache_k,
                    exchange_precision=spec.exchange_precision,
                    push_precision=spec.push_precision)
                self._stores[spec.name] = sh.HashStore(
                    self._shardings[spec.name],
                    self._initializers[spec.name])
                self._serving_stores[spec.name] = sh.HashStore(
                    self._shardings[spec.name])
            else:
                self._shardings[spec.name] = st.make_sharding_spec(
                    spec.meta(), mesh, num_shards=spec.num_shards,
                    layout=spec.layout, plane=spec.plane,
                    a2a_capacity=spec.a2a_capacity, a2a_slack=spec.a2a_slack,
                    cache_k=spec.cache_k,
                    exchange_precision=spec.exchange_precision,
                    push_precision=spec.push_precision)
                store = st.ArrayStore(self._shardings[spec.name])
                self._stores[spec.name] = store
                self._serving_stores[spec.name] = store

    # --- dirty tracking (delta checkpoints, checkpoint.py mode="delta") ----
    def enable_dirty_tracking(self, *, target_chunks: Optional[int] = None,
                              names=None) -> None:
        """Arm dirty tracking for every variable (idempotent): array
        tables to the row, hash tables to the key; ``target_chunks`` asks
        for ~that many chunks of either instead (contiguous rows, ``key %
        n``: ``dirty.py``).

        ``names``: restrict tracking to a subset of variables. ONLY for
        variables whose rows persist through their own path — the
        offload tier's ``ShardedOffloadedTable.persist`` is the case
        this exists for (its TrainState entry is a transient HBM cache;
        delta-chaining the cache would checkpoint residency noise, not
        the model). A delta save writes chunks for TRACKED variables
        only: an untracked variable that trains between the base and a
        restore silently reverts to its base rows — never exclude a
        variable something else doesn't durably own.

        Required before ``checkpoint.save_checkpoint(mode="delta")``:
        pushes mark chunks (the Trainer feeds every stepped batch's ids
        via :meth:`mark_dirty`; eager ``apply_gradients`` calls mark
        directly), and a delta save writes only the marked chunks —
        the reference's ICDE'23 incremental checkpoints from dirty
        tracking, generalized out of the offload tier (``dirty.py``).

        CUSTOM JITTED LOOPS: inside a jit the indices are tracers and
        cannot mark (the skip is deliberate and silent — marking at
        trace time would record once per COMPILE). A loop that jits its
        own step around ``apply_gradients`` must call
        ``collection.mark_dirty(batch["sparse"])`` host-side once per
        step, exactly as ``Trainer.train_step`` does — otherwise delta
        saves see nothing dirty and a chain restore silently reverts
        to the base.
        """
        from .dirty import make_array_tracker, make_hash_tracker
        if names is not None:
            unknown = set(names) - set(self.specs)
            if unknown:
                # a typo here would silently leave a variable untracked
                # and its trained rows reverting to base on a delta
                # restore — exactly the corruption mode above
                raise ValueError(
                    f"enable_dirty_tracking: unknown variable(s) "
                    f"{sorted(unknown)}; known: {sorted(self.specs)}")
        for name, spec in self.specs.items():
            if name in self._dirty_trackers:
                continue
            if names is not None and name not in names:
                continue
            if spec.use_hash:
                self._dirty_trackers[name] = make_hash_tracker(
                    name, spec.hash_capacity, target_chunks)
            else:
                self._dirty_trackers[name] = make_array_tracker(
                    name, spec.input_dim, target_chunks)

    @property
    def dirty_trackers(self) -> Dict[str, Any]:
        """``name -> DirtyTracker`` (empty unless tracking is enabled)."""
        return self._dirty_trackers

    def mark_dirty(self, sparse_inputs: Dict[str, Any]) -> None:
        """Mark the chunks a batch's pushes touched (host-side; a no-op
        unless tracking is enabled). Safe to over-mark — ids whose
        gradient was zero just cost delta bytes. Tracer inputs (an
        outer jit trace) are skipped: the Trainer marks from the HOST
        batch once per step instead, so marks count per step, not per
        compile."""
        if not self._dirty_trackers:
            return
        from . import hash_table as hash_lib
        from .dirty import KeyTracker
        done = {}       # tables fed one array (a fused table and its
        for name, idx in sparse_inputs.items():   # linear twin): one sort
            tracker = self._dirty_trackers.get(name)
            if tracker is None or idx is None:
                continue
            if isinstance(idx, jax.core.Tracer):
                continue
            spec = self.specs[name]
            exact = isinstance(tracker, KeyTracker)
            kind = (id(idx), "keys" if exact else spec.input_dim)
            if kind in done:    # (key % n chunks keep nothing here)
                if exact:
                    tracker.mark_keys(done[kind], distinct=True)
                else:
                    tracker.mark_rows(done[kind])
                continue
            arr = np.asarray(jax.device_get(idx)) \
                if isinstance(idx, jax.Array) else np.asarray(idx)
            if spec.use_hash:
                if spec.key_dtype == "wide" and arr.ndim >= 2 \
                        and arr.shape[-1] == 2:
                    pairs = arr.reshape(-1, 2)
                    keys = hash_lib.join64(pairs)
                    valid = pairs[:, 1] != hash_lib.empty_key(pairs.dtype)
                else:
                    valid = arr.ravel() != hash_lib.empty_key(arr.dtype)
                    keys = arr.astype(np.int64).ravel()
                if not exact:
                    tracker.mark_keys(keys)
                    continue
                # exact to the key: padding marks nothing
                done[kind] = np.unique(keys if valid.all() else keys[valid])
                tracker.mark_keys(done[kind], distinct=True)
            else:
                ids = arr.astype(np.int64).ravel()
                ids = np.unique(ids[(ids >= 0) & (ids < spec.input_dim)])
                done[kind] = ids
                tracker.mark_rows(ids)

    # --- introspection -----------------------------------------------------
    def variable_id(self, name: str) -> int:
        return self._variable_ids[name]

    def optimizer(self, name: str):
        return self._optimizers[name]

    def initializer(self, name: str):
        return self._initializers[name]

    def sharding_spec(self, name: str):
        return self._shardings[name]

    def cached_names(self) -> tuple:
        """Variables on the ``"a2a+cache"`` plane (hot-row replica)."""
        return tuple(name for name, s in self._shardings.items()
                     if s.is_cached)

    def grouped_names(self) -> tuple:
        """Variables on a grouped plane (collection-batched exchange,
        ``parallel/grouped.py``)."""
        return tuple(name for name, s in self._shardings.items()
                     if s.is_grouped)

    def make_hot_cache_manager(self, name: str):
        """Admission/refresh driver for one cached variable (the Trainer
        builds one per ``plane="a2a+cache"`` spec automatically)."""
        from .parallel import hot_cache
        spec = self.specs[name]
        sspec = self._shardings[name]
        if not sspec.is_cached:
            raise ValueError(f"{name!r} is not on the a2a+cache plane")
        return hot_cache.HotCacheManager(
            mesh=self.mesh, spec=sspec, k=sspec.cache_k,
            refresh_every=spec.cache_refresh_every,
            decay=spec.cache_decay, name=name)

    def model_meta(self, model_sign: str = "", model_uri: str = "") -> ModelMeta:
        variables = [
            ModelVariableMeta(meta=self.specs[name].meta(),
                              variable_id=self._variable_ids[name],
                              name=name)
            for name in self.specs
        ]
        variables.sort(key=lambda v: v.variable_id)
        # top-level num_shards is the max over variables (informational);
        # the exact per-variable counts ride in extra for mixed-plane models
        num_shards = max((s.num_shards for s in self._shardings.values()),
                         default=1)
        meta = ModelMeta(model_sign=model_sign, model_uri=model_uri,
                         variables=variables, num_shards=num_shards)
        meta.extra["variable_num_shards"] = {
            name: s.num_shards for name, s in self._shardings.items()}
        poolings = {name: s.pooling for name, s in self.specs.items()
                    if s.pooling}
        if poolings:
            # serving rebuilds specs from the meta alone; pooled lookups
            # must keep their combiner (registry._specs_from_meta)
            meta.extra["variable_pooling"] = poolings
        return meta

    # --- state lifecycle ---------------------------------------------------
    def init(self, rng: Optional[jax.Array] = None,
             only: Optional[Any] = None) -> Dict[str, Any]:
        """Materialize variables (each sharded over the mesh model axis).

        ``only`` restricts to a subset of names (the checkpoint loader skips
        device init for variables it overwrites host-side).
        """
        if rng is None:
            rng = jax.random.PRNGKey(0)

        # one jitted program for ALL variables: per-variable table creation
        # would compile one program per variable — 2F programs for an
        # F-feature model
        def _create_all(key):
            states = {}
            for name, spec in self.specs.items():
                if only is not None and name not in only:
                    continue
                sub = jax.random.fold_in(key, self._variable_ids[name])
                if spec.use_hash:
                    states[name] = sh.create_sharded_hash_table(
                        spec.meta(), self._optimizers[name],
                        mesh=self.mesh,
                        spec=self._shardings[name], rng=sub,
                        key_dtype=jnp.int32 if spec.key_dtype == "wide"
                        else jnp.dtype(spec.key_dtype),
                        wrap_cache=False)
                else:
                    states[name] = st.create_sharded_table(
                        spec.meta(), self._optimizers[name],
                        self._initializers[name], mesh=self.mesh,
                        spec=self._shardings[name], rng=sub,
                        wrap_cache=False)
            return states

        states = jax.jit(_create_all)(rng)
        # hot-row replicas attach eagerly (all-pad: zero hits until the
        # first HotCacheManager refresh admits keys)
        for name in states:
            states[name] = self.wrap_hot_cache(name, states[name])
        return states

    def wrap_hot_cache(self, name: str, table_state):
        """Attach derived per-plane state to a bare table state:
        an empty (all-pad) hot-row replica on the ``"a2a+cache"`` plane,
        an empty int8_ef push residual (``precision.EFState``) for
        ``push_precision="int8_ef"`` variables; pass-through otherwise.
        The checkpoint loader and serving restore use this too — both
        wrappers are derived state, never checkpointed (a restore
        forfeits at most one step of error feedback)."""
        from .parallel import hot_cache, precision
        sspec = self._shardings[name]
        # single-shard meshes have no wire: the push runs the exact
        # masked-local program and returns a bare table, so attaching a
        # wrapper here would flip the state pytree STRUCTURE after the
        # first push (a forced retrace under the donated step jit)
        if sspec.is_int8_ef and sspec.num_shards > 1 \
                and not isinstance(table_state, precision.EFState):
            # the key space the push dispatch sizes the residual in
            # (precision.ef_key_space): were the two to differ, sized_ef
            # would reset the residual every step
            return precision.empty_ef(
                table_state, dim=self.specs[name].output_dim,
                **self._stores[name].ef_space(table_state))
        return hot_cache.attach_empty(table_state, sspec, self.mesh)

    def state_shardings(self) -> Dict[str, Any]:
        """NamedShardings for every state leaf (for jit in/out_shardings)."""
        out = {}
        for name, spec in self.specs.items():
            sspec = self._shardings[name]
            mod = sh if spec.use_hash else st
            specs = mod.state_specs(self._optimizers[name],
                                    spec.output_dim, sspec)
            out[name] = st.state_shardings(specs, self.mesh)
        return out

    # --- data plane --------------------------------------------------------
    def _plans(self, name: str, batch_sharded: bool = True) -> bool:
        """:meth:`plan` covers ``name``'s column."""
        sspec = self._shardings[name]
        return not sspec.is_grouped and sharded.shares_plan(
            sspec, self.mesh, batch_sharded)

    def same_columns(self, inputs: Dict[str, Any]) -> SameColumns:
        """Observe, on the host, which columns of a step's ``inputs`` are
        the same ids, among those :meth:`plan` covers (none on the cached
        and grouped planes or under an ``int8_ef`` push: their step is the
        program it is without): the same object
        (what ``FusedMapper.fuse`` hands a table and its ``:linear`` twin),
        or host arrays equal in shape, dtype and value. The first of a
        group is the base of the others. Counters
        ``plan_columns_same_object`` / ``plan_columns_compared_equal`` /
        ``plan_columns_differ`` say what each later column was found to
        be."""
        by_object, by_look, twins = {}, {}, []
        for name, col in inputs.items():
            if not self._plans(name):
                continue
            seen = "plan_columns_differ" if by_object else None
            base = by_object.get(id(col))
            if base is not None:
                seen = "plan_columns_same_object"
            elif isinstance(col, np.ndarray):
                # value against value only where the first ids agree: a
                # model of many features pays a look a column, not a pass
                # over every pair
                alike = by_look.setdefault(
                    (col.shape, col.dtype.str, col.flat[:4].tobytes()), [])
                base = next((b for b in alike
                             if np.array_equal(col, inputs[b])), None)
                if base is not None:
                    seen = "plan_columns_compared_equal"
                else:
                    alike.append(name)
            if base is None:
                by_object[id(col)] = name
            else:
                twins.append((name, base))
            if seen:
                observability.GLOBAL.add(seen, 1)
        return SameColumns(tuple(twins))

    def plan(self, inputs: Dict[str, jnp.ndarray], *,
             batch_sharded: bool = True) -> Dict[str, Any]:
        """One step's dedup of every input column whose table's pull and
        push can share it (``sharded.shares_plan``: the masked-local body,
        which is every plane on one chip but the cached one, on a mesh
        with no data axis to gather over; the plain ``a2a`` exchange over
        several shards): name -> ``dedup.Plan``, or the routed body's
        ``alltoall.RoutedPlan`` (the sender's dedup and buckets, the keys
        sent, the owner's dedup of what it received), for :meth:`pull` and
        :meth:`apply_gradients` of the SAME ``inputs``. The pull then
        resolves each distinct key once and the push deduplicates nothing
        again; rows and updates are what they are without. A column left
        out (the cached plane, an ``int8_ef`` push, the grouped planes)
        runs as it does without.

        One plan a distinct column: tables handed the SAME array (inside a
        jit, the same traced value: :meth:`SameColumns.bind`) in the same
        key form (``sharded.plan_form``: array ids, int32 hash keys, wide
        pairs; on the routed body the owners' layout and the buckets'
        size too) get the same plan object, built once; each store still
        lays its own ownership mask over it and finds its own slots. Under
        ``record_stats`` a step counts ``dedup_plans_built`` and
        ``dedup_plan_tables``."""
        plans, built = {}, {}
        for name, idx in inputs.items():
            if not self._plans(name, batch_sharded):
                continue
            store = self._stores[name]
            keys = self._widen(self.specs[name], idx)
            column = id(idx), sharded.plan_form(store, keys)
            if column not in built:
                built[column] = sharded.plan_sharded(
                    keys, mesh=self.mesh, store=store,
                    batch_sharded=batch_sharded)
            plans[name] = built[column]
        if plans and observability.evaluate_performance():
            table_lib.record_stat("dedup_plans_built",
                                  jnp.int32(len(built)), True)
            table_lib.record_stat("dedup_plan_tables",
                                  jnp.int32(len(plans)), True)
        return plans

    def pull(self, states: Dict[str, Any], inputs: Dict[str, jnp.ndarray],
             *, batch_sharded: bool = True,
             read_only: bool = False,
             serving_rows: bool = False,
             plan: Optional[Dict[str, Any]] = None
             ) -> Dict[str, jnp.ndarray]:
        """Lookup rows for every (present) input column.

        ``inputs``: name -> integer indices of any shape; returns name ->
        rows shaped ``indices.shape + (dim,)``. Differentiation happens with
        respect to the *returned rows* (pass their grads to
        :meth:`apply_gradients`), not the tables — mirroring the reference's
        custom PullWeights gradient (exb.py:89-97). ``read_only`` selects the
        serving contract: unknown hash keys return zeros instead of init rows
        (reference EmbeddingPullOperator read_only get_weights path).
        ``serving_rows`` selects the ROW contract of the serving data plane:
        one row per index (pair), no pooling, and any trailing dim of 2 on a
        wide spec IS a pair axis — the shape a routing client fans out is
        always a flat pair list, never a ``[B, L=2]`` sequence (a pooled
        spec's training-side heuristic would misread it). ``plan`` is
        :meth:`plan`'s of these ``inputs``.
        """
        return self.pull_resolved(
            states, inputs, batch_sharded=batch_sharded, read_only=read_only,
            serving_rows=serving_rows, plan=plan)[0]

    def pull_resolved(self, states: Dict[str, Any],
                      inputs: Dict[str, jnp.ndarray],
                      *, batch_sharded: bool = True,
                      read_only: bool = False,
                      serving_rows: bool = False,
                      plan: Optional[Dict[str, Any]] = None) -> tuple:
        """:meth:`pull`, and what it resolved: ``(rows, resolved)``.

        ``resolved``: name -> ``dedup.Resolution`` for every column the
        ``plan`` covers, what its table's pull found for the plan's distinct
        keys (each shard's rows as read, a hash table's slots). It is for
        :meth:`apply_gradients` of the SAME inputs, plan and states, with
        no write to the tables in between: the push then resolves nothing a
        second time. Under ``read_only`` a missing hash key reads zeros, not
        the row its insert writes, and nothing is handed on.
        """
        plan = plan or {}
        resolved = {}
        widened = {
            name: self._widen(self.specs[name], idx,
                              pair_ndim=2 if serving_rows else None)
            for name, idx in inputs.items()}
        # grouped-plane columns batch into ONE exchange per group
        # (parallel/grouped.py) instead of one pipeline per table; the
        # raw rows come back per name and pool below like any other
        grouped_idx = {name: idx for name, idx in widened.items()
                       if self._shardings[name].is_grouped}
        raw = {}
        if grouped_idx:
            from .parallel import grouped
            raw = grouped.pull_grouped(self, states, grouped_idx,
                                       read_only=read_only,
                                       batch_sharded=batch_sharded)
        rows = {}
        for name, idx in widened.items():
            spec = self.specs[name]
            if name in raw:
                r = raw[name]
            else:
                stores = self._serving_stores if read_only else self._stores
                r = sharded.pull_sharded(
                    states[name], idx, mesh=self.mesh, store=stores[name],
                    batch_sharded=batch_sharded, plan=plan.get(name))
                if name in plan:
                    r, found = r
                    if not read_only:
                        resolved[name] = found
            if spec.pooling and not serving_rows:
                # wide sequence features carry [B, L, 2] pair ids; the
                # combiner counts validity on the hi word (ragged.py)
                r = ragged.pool_rows(r, idx, spec.pooling,
                                     ragged.pad_id_for(spec),
                                     self._pool_vocab(spec),
                                     wide=spec.key_dtype == "wide")
            rows[name] = r
        return rows, resolved

    def _pool_vocab(self, spec: EmbeddingSpec) -> Optional[int]:
        return None if spec.use_hash else spec.input_dim

    def _widen(self, spec: EmbeddingSpec, idx,
               pair_ndim: Optional[int] = None) -> jnp.ndarray:
        """Bridge plain id columns onto wide (pair-keyed) tables.

        Wide tables take ``[..., 2]`` pairs; a NARROW integer input
        (flat ``[B]`` ids, or a ``[B, L]`` padded matrix for pooled
        features) is widened so int32/int64 pipelines run unchanged
        against the default wide key space. HOST int64 columns are split
        on host (``hash_table.split64``) BEFORE any jnp conversion — with
        x64 off ``jnp.asarray`` would silently truncate them to int32 and
        address the wrong rows; device arrays widen on device
        (``hash_table.widen_ids``). Inputs already shaped as pairs pass
        through. Ambiguity rule: a trailing dim of 2 IS a pair axis (for
        pooled specs only at ndim >= 3, since their ``[B, L=2]`` matrices
        are sequences) — feed genuinely 2-wide narrow shapes through
        ``split64`` instead. Callers with an unambiguous wire contract
        (the serving row plane, whose queries are always flat pair lists)
        pass ``pair_ndim=2`` to override the pooled-spec heuristic.
        """
        if not spec.use_hash or spec.key_dtype != "wide":
            return idx
        from . import hash_table as hash_lib
        if pair_ndim is None:
            pair_ndim = 3 if spec.pooling else 2
        if not isinstance(idx, jax.Array):
            arr = np.asarray(idx)
            is_pairs = arr.ndim >= pair_ndim and arr.shape[-1] == 2
            if arr.dtype.kind in "iu" and arr.dtype.itemsize == 8:
                if is_pairs:
                    # 64-bit-typed pair WORDS: values must fit int32 (a
                    # raw 64-bit id belongs in split64, not a pair word)
                    if arr.size and (arr.max() > np.iinfo(np.int32).max
                                     or arr.min() < np.iinfo(np.int32).min):
                        raise ValueError(
                            f"embedding {spec.name!r}: pair words exceed "
                            "int32 — pass hash_table.split64(ids), not "
                            "raw 64-bit ids shaped as pairs")
                    return jnp.asarray(arr.astype(np.int32))
                # host split keeps full 64-bit width with x64 OFF; the
                # int64 sentinel (INT64_MIN) splits into the EMPTY band,
                # staying invalid by the hi-word rule
                return jnp.asarray(hash_lib.split64(arr))
            idx = jnp.asarray(arr)
        if idx.ndim >= pair_ndim and idx.shape[-1] == 2:
            return idx
        return hash_lib.widen_ids(idx)

    def apply_gradients(self, states: Dict[str, Any],
                        inputs: Dict[str, jnp.ndarray],
                        row_grads: Dict[str, jnp.ndarray],
                        *, batch_sharded: bool = True,
                        plan: Optional[Dict[str, Any]] = None,
                        resolved: Optional[Dict[str, Any]] = None
                        ) -> Dict[str, Any]:
        """Push+update for every column present in ``row_grads``.

        ``row_grads[name]`` has the shape of the pulled rows. Untouched
        variables keep their state object unchanged. ``plan`` is
        :meth:`plan`'s of these ``inputs``, the one their pull ran on;
        ``resolved`` is what that pull handed on (:meth:`pull_resolved`),
        ``states`` being the tables it read.
        """
        plan = plan or {}
        resolved = resolved or {}
        # delta-checkpoint dirty marks for EAGER pushes (tracer inputs —
        # the jitted Trainer step — skip; the Trainer marks host-side)
        self.mark_dirty({n: inputs.get(n) for n in row_grads})
        new_states = dict(states)
        grouped_idx: Dict[str, jnp.ndarray] = {}
        grouped_grads: Dict[str, jnp.ndarray] = {}
        for name, g in row_grads.items():
            spec = self.specs[name]
            idx_in = self._widen(spec, inputs[name])
            if spec.pooling:
                # pooled features carry [B, dim] grads; expand with the
                # pooling VJP so each valid slot updates like a raw lookup
                g = ragged.expand_pooled_grads(
                    g, idx_in, spec.pooling, ragged.pad_id_for(spec),
                    self._pool_vocab(spec),
                    wide=spec.key_dtype == "wide")
            if self._shardings[name].is_grouped:
                # collection-batched push: one pre-reduced exchange per
                # GROUP (parallel/grouped.py), per-table optimizers
                # applied server-side
                grouped_idx[name] = idx_in
                grouped_grads[name] = g
                continue
            new_states[name] = sharded.apply_gradients_sharded(
                states[name], self._optimizers[name], idx_in, g,
                mesh=self.mesh, store=self._stores[name],
                batch_sharded=batch_sharded, plan=plan.get(name),
                resolved=resolved.get(name))
        if grouped_idx:
            from .parallel import grouped
            new_states.update(grouped.apply_gradients_grouped(
                self, states, grouped_idx, grouped_grads,
                batch_sharded=batch_sharded))
        return new_states

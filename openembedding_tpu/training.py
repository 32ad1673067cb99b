"""Training loop machinery: TrainState + jitted SPMD train/eval steps.

TPU-native counterpart of the reference's execution model (SURVEY §3.2/3.3):
the reference splits a step into pull RPCs (forward), push RPCs (backward),
a Horovod allreduce of dense grads + fake grads (barrier), and a store RPC
(optimizer commit). Here the whole step is ONE jitted SPMD program over the
(data, model) mesh:

* forward pull  -> shard_map gather + psum        (was: pull RPC)
* dense grads   -> XLA all-reduce over data axis  (was: Horovod allreduce)
* sparse update -> all_gather + masked local scatter-apply (was: push+store)
* batch barrier -> implicit: it's one XLA program (was: fake-grad allreduce,
  exb_ops.cpp:434-437)

The dense half (MLPs + small `sparse_as_dense` embeddings) is a plain flax
module optimized by optax, replicated like the reference's worker-side
tf.Variables (exb.py:100-104, README "Cache" mode).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from .analysis import scope
from .analysis.concurrency import sync_point
from .analysis.retrace import LEDGER, RetraceGuard
from .utils import observability
from .embedding import EmbeddingCollection, SameColumns
from .parallel.mesh import DATA_AXIS


# the key under which a placed batch carries its ``SameColumns`` to the
# serial step program (static: no array)
SAME_COLUMNS = "same_columns"


@struct.dataclass
class TrainState:
    """Whole-model training state: dense + sparse + bookkeeping."""

    step: jnp.ndarray            # int32 global step (the reference batch_id)
    params: Any                  # flax dense params, replicated
    opt_state: Any               # optax state for the dense params
    emb: Dict[str, Any]          # embedding states (sharded over model
                                 # axis). push_precision="int8_ef"
                                 # variables carry their quantization
                                 # residual here as precision.EFState —
                                 # the error-feedback state rides the
                                 # TrainState and is donated with it
                                 # (derived: never checkpointed)


def binary_logloss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean sigmoid cross-entropy — the CTR objective of every reference
    example (examples/criteo_deepctr_network.py 'binary_crossentropy')."""
    logits = logits.reshape(-1)
    labels = labels.reshape(-1).astype(logits.dtype)
    return jnp.mean(optax.sigmoid_binary_cross_entropy(logits, labels))


class _BackgroundSave:
    """The writer thread of one autosave: ``checkpoint_delta.finish_delta``
    of a snapshot ``fit`` has taken. Owned by the step thread, which joins
    it before the next snapshot, on ``fit``'s return and on its unwind."""

    def __init__(self, pending):
        self.err: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, args=(pending,), daemon=False,
            name="oe-ckpt-autosave")
        self._thread.start()

    def _run(self, pending) -> None:
        from . import checkpoint_delta as cd
        try:
            cd.finish_delta(pending)
        except BaseException as e:  # noqa: BLE001 — re-raised at join
            self.err = e

    def join(self) -> None:
        self._thread.join()
        if self.err is not None:
            raise RuntimeError(
                "autosave failed; its rows are marked dirty again and "
                "ride the next save") from self.err


class Trainer:
    """Builds jitted train/eval steps for (flax module + EmbeddingCollection).

    ``module.apply({'params': p}, batch['dense'], rows)`` must return logits
    of shape [B]. ``batch`` is ``{'label': [B], 'dense': [B, d] (optional),
    'sparse': {name: int indices}}``, batch-sharded over the data axis.
    """

    def __init__(self, module, collection: EmbeddingCollection,
                 dense_optimizer: optax.GradientTransformation,
                 loss_fn: Callable = binary_logloss,
                 sparse_as_dense: Optional[Any] = None,
                 offload: Optional[Dict[str, Any]] = None,
                 pipeline_depth: int = 4):
        """``sparse_as_dense``: DenseFeatureSpecs (from
        ``hybrid.split_sparse_dense``) kept as flax params inside the model —
        the reference's "Cache" hybrid. Batch ``sparse`` columns are routed
        by name: dense-kept features never touch the sharded path.

        ``offload``: name -> ShardedOffloadedTable for variables whose host
        store exceeds HBM (the reference's PMem tier). The variable's cache
        state lives in ``TrainState.emb`` like any hash variable; the
        Trainer auto-prepares each batch's rows before the jitted step and
        records dirty marks after it (PmemEmbeddingOptimizerVariable.h's
        pre-touch + work advance). A tier built with a ``vocab`` takes
        bounded int ids; one built without holds 64-bit keys (int64 host
        columns or ``[..., 2]`` int32 pairs) and a key no step has seen
        is born in the step, as in an all-in-HBM hash table. Tables fed
        one column (``fields`` and ``fields:linear``) share the pass
        that makes a batch's ids distinct.

        ``pipeline_depth``: how many batches of offload host-prepare may
        run ahead of the device (the reference's prefetch ``steps``
        budget, exb_ops.cpp:109-205 attr :148-156). Depth K keeps K
        prepared batches in flight so a host prepare slower than the
        device step still overlaps across the window; 1 restores the
        single-lookahead pipeline; results are bit-identical at any
        depth (the planned-residency chain in offload.host_prepare).
        Default 4: cold host pages amortize across a deeper window (the
        reference's default budget is deeper still, 64). On the chip at
        depth 4 the step loop waits 0.004 ms a step for the lookahead
        thread (``train_offload_wait_ms_per_step``, cell
        ``deepfm_dim9_offload.train_zipf_offload``: a host prepare of 8.1
        ms against a 71.5 ms device step; PERF.md, PR 28)."""
        if sparse_as_dense:
            from .hybrid import HybridModel
            module = HybridModel(inner=module,
                                 dense_specs=tuple(sparse_as_dense))
            self._dense_names = frozenset(
                s.name for s in sparse_as_dense)
        else:
            self._dense_names = frozenset()
        self.module = module
        self.collection = collection
        self.tx = dense_optimizer
        self.loss_fn = loss_fn
        self.offload = dict(offload or {})
        for oname in self.offload:
            if oname not in collection.specs:
                raise ValueError(
                    f"offloaded variable {oname!r} is not in the collection; "
                    "register table.embedding_spec() in its specs")
        self.mesh = collection.mesh
        # serving signature: "<uuid>-<version>", version == step — the
        # reference's model_version variable bumped per optimizer step and
        # stamped at save (exb.py:213-218, py_api.cc:130-138)
        import uuid as _uuid
        self.model_uuid = _uuid.uuid4().hex[:12]
        self._replicated = NamedSharding(self.mesh, P())
        self._batch_sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        self._train_step = None
        # what the last serial step's batch was observed to be
        # (``_place_step_batch``): the program ``lower_train_step`` lowers
        self._same_columns = SameColumns()
        self._eval_step = None
        # hot-row replica admission drivers, one per "a2a+cache" variable:
        # the frequency sketch observes every stepped batch and the replica
        # refreshes every cache_refresh_every steps OUTSIDE the jitted step
        # (parallel/hot_cache.py)
        self._hot = {name: collection.make_hot_cache_manager(name)
                     for name in collection.cached_names()}
        # ':linear' twins observe the SAME id column as their base
        # variable — share one sketch so the per-step host count (and the
        # per-window decay) runs once; each twin keeps its own replica
        for name, mgr in self._hot.items():
            if name.endswith(":linear"):
                base = self._hot.get(name[: -len(":linear")])
                if base is not None:
                    mgr.share_sketch(base)
        self.pipeline_depth = max(1, int(pipeline_depth))
        # in-flight lookahead prepares, oldest first; each entry's thread
        # CHAINS on the previous one, so host_prepare calls run strictly
        # in batch order (the planned-residency bookkeeping requires it)
        self._preps: "deque" = deque()
        # host-side step counter for graftscope step spans (the device
        # state.step is a device array — reading it back per step would
        # add a sync round trip to every step)
        self._host_step = 0
        self._autosave: Optional[_BackgroundSave] = None

    # --- initialization ----------------------------------------------------
    def _split_sparse(self, sparse: Dict[str, Any]):
        """Route batch columns: sharded-path inputs vs dense-kept ids."""
        if not self._dense_names:
            return sparse, None
        pull = {k: v for k, v in sparse.items() if k not in self._dense_names}
        dense_ids = {k: v for k, v in sparse.items()
                     if k in self._dense_names}
        return pull, dense_ids

    def _apply(self, params, dense, rows, dense_ids):
        if self._dense_names:
            return self.module.apply({"params": params}, dense, rows,
                                     dense_ids)
        return self.module.apply({"params": params}, dense, rows)

    def init(self, rng: jax.Array, sample_batch: Dict[str, Any]) -> TrainState:
        """Initialize dense params (replicated) + all embedding tables."""
        emb_rng, dense_rng = jax.random.split(rng)
        emb = self.collection.init(emb_rng)
        pull_inputs, dense_ids = self._split_sparse(sample_batch["sparse"])
        # dense init only needs row SHAPES — zeros via eval_shape avoid
        # dispatching one pull program per variable before training starts
        row_shapes = jax.eval_shape(
            lambda e, s: self.collection.pull(e, s, batch_sharded=False),
            emb, pull_inputs)
        rows = jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype),
                            row_shapes)
        if self._dense_names:
            variables = self.module.init(dense_rng,
                                         sample_batch.get("dense"), rows,
                                         dense_ids)
        else:
            variables = self.module.init(dense_rng,
                                         sample_batch.get("dense"), rows)
        params = variables["params"]
        set_repl = partial(jax.device_put, device=self._replicated)
        params = jax.tree.map(set_repl, params)
        opt_state = jax.tree.map(set_repl, self.tx.init(params))
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=opt_state, emb=emb)

    # --- steps ---------------------------------------------------------------
    def _dense_update_and_push(self, state: TrainState, batch, rows,
                               pull_inputs, dense_ids, plan=None,
                               resolved=None):
        """The step program's core: loss + grads on ``rows``, dense
        optimizer update, sparse push. ``plan`` is the step's
        ``collection.plan`` of ``pull_inputs``, the one ``rows`` were
        pulled with, and ``resolved`` what that pull resolved
        (``collection.pull_resolved``): the push takes it."""
        def lfn(params, rows):
            logits = self._apply(params, batch.get("dense"), rows,
                                 dense_ids)
            return self.loss_fn(logits, batch["label"])

        # value_and_grad, taken apart so that the forward and the backward
        # pass are each a named stage of the step program
        loss, backward = scope.stage("dense_fwd")(
            lambda params, rows: jax.vjp(lfn, params, rows))(
                state.params, rows)
        dense_g, row_g = scope.stage("dense_bwd")(
            lambda backward, loss: backward(jnp.ones_like(loss)))(
                backward, loss)

        @scope.stage("dense_update")
        def update(dense_g, opt_state, params):
            updates, opt_state = self.tx.update(dense_g, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        params, opt_state = update(dense_g, state.opt_state, state.params)
        emb = self.collection.apply_gradients(
            state.emb, pull_inputs, row_g, plan=plan, resolved=resolved)
        return params, opt_state, emb, loss

    def lower_train_step(self, state: TrainState, batch):
        """The serial step program lowered for ``state`` and ``batch`` —
        the same jitted function :meth:`train_step` runs, for
        ``compile().memory_analysis()`` and compiled-HLO audits. ``batch``
        is placed as :meth:`shard_batch` places it; ShapeDtypeStructs that
        carry the shardings lower it from shapes alone (AOT for a described
        topology, ``tests/test_tpu_lowering.py``). Which of its columns
        are the same ids shapes cannot say: the program is the one the
        last :meth:`train_step` ran (the two-plan one if none has), or the
        one of the ``SameColumns`` that ``batch`` brings under
        ``SAME_COLUMNS``."""
        if self._train_step is None:
            self._train_step = self._build_train_step()
        if SAME_COLUMNS not in batch:
            batch = {**batch, SAME_COLUMNS: self._same_columns}
        return self._train_step.lower(state, batch)

    def _build_train_step(self):
        collection = self.collection

        def step_fn(state: TrainState, batch) -> tuple:
            pull_inputs, dense_ids = self._split_sparse(batch["sparse"])
            # columns the host saw to be the same ids are ONE traced
            # column (two parameters of equal value are two to the
            # compiler; a twin's own is unused, and pruned)
            pull_inputs = batch.get(SAME_COLUMNS, SameColumns()).bind(
                pull_inputs)
            # a column's ids are deduplicated once a step, in front of
            # the pull: pull and push of every table that reads it work
            # on the distinct keys
            plan = collection.plan(pull_inputs)
            # what the pull resolved for the distinct keys (a slot, a
            # weight row) goes to the push with the plan: nothing writes
            # the tables between the two
            rows, resolved = collection.pull_resolved(
                state.emb, pull_inputs, plan=plan)
            # The push's insert needs nothing of the dense pass: only the
            # plan, the slots the pull found and the key array the pull's
            # find reads. This edge says the push follows the pull; left
            # to order the two itself the v5e compiler copies an int32 key
            # array (256 MiB a table) into the insert loop
            # (tests/test_tpu_lowering.py).
            if plan:
                rows, plan, resolved = jax.lax.optimization_barrier(
                    (rows, plan, resolved))
            params, opt_state, emb, loss = self._dense_update_and_push(
                state, batch, rows, pull_inputs, dense_ids, plan, resolved)
            new_state = TrainState(step=state.step + 1, params=params,
                                   opt_state=opt_state, emb=emb)
            return new_state, {"loss": loss}

        return jax.jit(step_fn, donate_argnums=(0,))

    def _build_eval_step(self):
        collection = self.collection

        def eval_fn(state: TrainState, batch):
            pull_inputs, dense_ids = self._split_sparse(batch["sparse"])
            rows = collection.pull(state.emb, pull_inputs)
            logits = self._apply(state.params, batch.get("dense"), rows,
                                 dense_ids)
            return jax.nn.sigmoid(logits.reshape(-1))

        return jax.jit(eval_fn)

    def train_step(self, state: TrainState, batch, *,
                   next_batch=None) -> tuple:
        """One step. With ``next_batch``, the HOST half of the
        next batch's offload prepare (residency math + host-store row
        gather) runs on a background thread WHILE the device executes this
        step — the reference's PrefetchPullWeights issuing pulls ahead of
        the graph (exb_ops.cpp:109-205). The device-insert half is applied
        just before the next step consumes it, so step time approaches
        max(host prepare, device step) instead of their sum. ``fit`` keeps
        up to ``pipeline_depth`` prepared batches in flight automatically;
        callers driving steps by hand pass ``next_batch`` themselves (or
        skip it and keep the serial path). On the device the step is the
        same program either way: ``next_batch`` only feeds the lookahead.
        """
        if self._train_step is None:
            self._train_step = self._build_train_step()
        # graftscope: one span per whole host-visible step, with
        # StepTraceAnnotation pass-through so a concurrent jax.profiler
        # device trace attributes its work to the same step numbers. Its
        # three children carry that number: placing the batch, dispatching
        # the jitted program, and the bookkeeping on both sides of it
        at = {"step": self._host_step}
        try:
            with scope.step_span(self._host_step):
                with scope.span("trainer.bookkeeping", detail=at):
                    # per-table batch-shape stats (pull_indices/
                    # pull_unique counters + pull_rows/unique_ratio/
                    # key_skew histograms); gated inside — a host
                    # np.unique per column, off by default like the
                    # reference's accumulators
                    observability.record_batch_stats(batch["sparse"])
                    state, uniqs = self._apply_prepared_offload(state,
                                                                batch)
                with scope.span("trainer.place_batch", detail=at):
                    placed = self._place_step_batch(batch)
                with scope.span("trainer.dispatch", detail=at):
                    state, metrics = self._train_step(state, placed)
                with scope.span("trainer.bookkeeping", detail=at):
                    if self.collection.dirty_trackers:
                        # delta-checkpoint dirty marks from the HOST
                        # batch: the jitted step's in-trace ids are
                        # tracers, so the collection cannot mark there
                        # (once per compile); here marks land once per
                        # step
                        cols, _ = self._split_sparse(batch["sparse"])
                        self.collection.mark_dirty(cols)
                    for name, table in self.offload.items():
                        table.note_update(batch["sparse"][name],
                                          uniq=uniqs.get(name))
                    state = self._note_hot_cache(state, batch)
                    if next_batch is not None and self.offload \
                            and not self._prep_started(next_batch):
                        self._start_host_prepare(next_batch)
        finally:
            # advance on ERROR exits too: a caller that catches and
            # retries must not reuse the step number (duplicate ids in
            # the trace + wrong device-profile attribution)
            self._host_step += 1
        return state, metrics

    def _note_hot_cache(self, state: TrainState, batch) -> TrainState:
        """Feed the hot-row admission sketches with this batch's keys and
        refresh due replicas (host-side; the refresh re-gathers rows from
        the authoritative table — never a writeback)."""
        if not self._hot:
            return state
        emb = None
        counted = set()
        for name, mgr in self._hot.items():
            col = batch["sparse"].get(name)
            if col is None:
                continue
            if id(mgr.sketch) in counted:
                mgr.tick()      # shared sketch: already counted this step
            else:
                mgr.observe(col)
                counted.add(id(mgr.sketch))
            if mgr.due:
                if emb is None:
                    emb = dict(state.emb)
                emb[name] = mgr.refresh(emb[name])
        if emb is not None:
            state = state.replace(emb=emb)
        return state

    def _prep_started(self, batch) -> bool:
        return any(e[1] is batch for e in self._preps)

    def prefetch(self, batches) -> None:
        """Queue offload host-prepares for upcoming batches — the current
        batch plus up to ``pipeline_depth`` ahead (``fit`` does this
        automatically; hand-driven loops call it before each
        ``train_step``, mirroring the reference's explicit prefetch op,
        exb_ops.cpp:109-205). Order matters: pass batches in the order
        they will be stepped, starting with the batch about to run."""
        if not self.offload:
            return
        for b in list(batches)[: self.pipeline_depth + 1]:
            if b is not None and not self._prep_started(b):
                self._start_host_prepare(b)

    def _start_host_prepare(self, batch) -> None:
        """Queue the host-only prepare of ``batch`` on a background
        thread (one thread covering every offloaded table, in registration
        order). Threads CHAIN: each joins its predecessor before running,
        so prepares execute strictly in batch order no matter how many
        are in flight — offload.host_prepare's planned-residency math is
        only correct under that serialization. Results are picked up — and
        the thread joined — when ``_apply_prepared_offload`` reaches this
        batch."""
        prev = self._preps[-1][0] if self._preps else None
        results: Dict[str, Any] = {}
        err: list = []
        # the step this batch is prepared for: batches queue in step order
        at = {"step": self._host_step + len(self._preps)}

        def _run():
            if prev is not None:
                prev.join()
            try:
                sync_point("trainer.prep.run")
                seen: Dict[Any, Any] = {}
                for name in self.offload:
                    results[name] = self._host_prepare(name, batch, at,
                                                       seen)
            except BaseException as e:  # noqa: BLE001 — re-raised at join
                err.append(e)

        t = threading.Thread(target=_run, daemon=True, name="oe-prep")
        t.start()
        self._preps.append((t, batch, results, err))

    def _distinct(self, name: str, col, seen: Dict[Any, Any]):
        """``col``'s distinct ids in ``name``'s id space, made once for
        all the tables that share both (``seen``; the column is held while
        ``seen`` lives, so its id is its own)."""
        table = self.offload[name]
        space = (id(col), table.vocab)
        if space not in seen:
            seen[space] = table.distinct(col)
        return seen[space]

    def _host_prepare(self, name: str, batch, at, seen: Dict[Any, Any]):
        """``host_prepare`` of one offloaded table for ``batch``. Tables
        fed the SAME column (the same array object, which is what
        ``FusedMapper.fuse`` hands ``fields`` and ``fields:linear``) over
        the same id space share the pass that makes its ids distinct
        (``seen``: one ``np.unique`` a column a batch, and for a keyed
        tier one joining of its pairs into int64 keys)."""
        table = self.offload[name]
        col = batch["sparse"][name]
        with scope.span("offload.host_prepare", detail=at, table=name):
            return table.host_prepare(self._distinct(name, col, seen),
                                      distinct=True,
                                      lookups=table.lookups_of(col))

    def _cancel_preps(self) -> None:
        """Abandon every in-flight prepare (the caller is about to step a
        batch the lookahead window didn't predict, or is unwinding).
        Cancels oldest-first and covers the WHOLE window — later prepares'
        miss sets assume the earlier ones' planned inserts."""
        while self._preps:
            t, _, results, err = self._preps.popleft()
            t.join()
            for name, prep in results.items():
                self.offload[name].cancel_prepared(prep)
            # a failed abandoned prepare left no planned marks (offload
            # marks only after success); nothing further to unwind

    def _apply_prepared_offload(self, state: TrainState, batch):
        """Apply this batch's prepared inserts (from the lookahead window
        when its OLDEST entry prepared exactly this batch, else cancel the
        window and prepare synchronously)."""
        if not self.offload:
            return state, {}
        prepped = None
        at = {"step": self._host_step}
        if self._preps and self._preps[0][1] is batch:
            t, _, results, err = self._preps.popleft()
            # the host time of the tier that is exposed: the step loop
            # blocked on the lookahead thread
            with scope.span("offload.wait_prepare", detail=at):
                t.join()
            if err:
                # release the tables this entry DID prepare, then the rest
                # of the window (its math built on this entry's marks)
                for name, prep in results.items():
                    self.offload[name].cancel_prepared(prep)
                self._cancel_preps()
                raise RuntimeError("background offload prepare failed") \
                    from err[0]
            prepped = results
        else:
            self._cancel_preps()
        emb = dict(state.emb)
        uniqs: Dict[str, Any] = {}
        names = list(self.offload)
        seen: Dict[Any, Any] = {}
        for i, name in enumerate(names):
            table = self.offload[name]
            prep = prepped.get(name) if prepped is not None else None
            if prep is None:    # nothing looked ahead: prepared in line
                prep = self._host_prepare(name, batch, at, seen)
            try:
                emb[name] = table.apply_prepared(emb[name], prep)
            except BaseException:
                # release the NOT-YET-APPLIED preps of this entry (the
                # raiser's own marks were restored by its unwind or were
                # never transferred) plus the lookahead window — a caller
                # that survives the error must not inherit leaked planned
                # marks that would degrade every later prepare to the
                # evict path. Applied tables' preps are NOT cancelled
                # (their marks were already transferred to resident).
                table.cancel_prepared(prep)
                if prepped is not None:
                    for later in names[i + 1:]:
                        lp = prepped.get(later)
                        if lp is not None:
                            self.offload[later].cancel_prepared(lp)
                self._cancel_preps()
                raise
            uniqs[name] = prep.uniq
        return state.replace(emb=emb), uniqs

    def prepare_offload(self, state: TrainState, batch) -> TrainState:
        """Pre-touch offloaded rows for this batch (host->HBM cache inserts).

        train_step calls this automatically; for evaluation, call it
        yourself and eval with the returned state:

            state = trainer.prepare_offload(state, batch)
            scores = trainer.eval_step(state, batch)
        """
        if not self.offload:
            return state
        state, _ = self._apply_prepared_offload(state, batch)
        return state

    def eval_step(self, state: TrainState, batch) -> jnp.ndarray:
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        if not observability.evaluate_performance():
            # default path stays async — the span would otherwise need a
            # block_until_ready, serializing the dispatch pipeline
            return self._eval_step(state, self.shard_batch(batch))
        with scope.span("eval"):
            out = self._eval_step(state, self.shard_batch(batch))
            jax.block_until_ready(out)
            return out

    # --- helpers -------------------------------------------------------------
    def shard_batch(self, batch):
        """Place host batch arrays batch-sharded over the data axis — each
        device takes its slice straight from host memory (a jnp.asarray
        first would commit the whole batch to device 0 and reshard)."""
        return jax.device_put(batch, self._batch_sharding)

    def _place_step_batch(self, batch):
        """:meth:`shard_batch` for the serial step program, with what the
        host sees of its sparse columns and the program cannot: which of
        them are the same ids (``EmbeddingCollection.same_columns``). The
        note is static, so the step is compiled once a value: tables fed
        one column run ONE ``dedup.Plan``, a batch whose columns differ
        the program with a plan each."""
        cols, _ = self._split_sparse(batch["sparse"])
        self._same_columns = self.collection.same_columns(cols)
        return {**self.shard_batch(batch), SAME_COLUMNS: self._same_columns}

    def model_sign(self, state: TrainState) -> str:
        """Version-stamped serving signature for this state."""
        return f"{self.model_uuid}-{int(jax.device_get(state.step))}"

    def fit(self, state: TrainState, batches, *, log_every: int = 0,
            log_fn=print, persist_dir: Optional[str] = None,
            retrace_budget: Optional[int] = None,
            autosave_every: int = 0,
            autosave_dir: Optional[str] = None,
            resume_from: Optional[str] = None):
        """Simple host loop over an iterable of batches (model.fit analogue).

        Keeps up to ``pipeline_depth`` batches of offload host-prepare in
        flight ahead of the device (see :meth:`train_step` and
        ``pipeline_depth`` in the constructor).

        ``retrace_budget``: programs XLA may compile, or fetch from the
        persistent compile cache (a fetched program counts: the loop
        stopped to load it all the same), after a TWO-step
        warmup (step 1 compiles the step program; step 2 may legally
        recompile once — its input is step 1's output, whose shardings/
        layouts can differ from the init-time state). A steady-state
        loop should need 0 unless it refreshes hot-row replicas or
        inserts offload chunks of new sizes; a budget trip raises
        :class:`analysis.retrace.RetraceBudgetExceeded` at the end of
        the loop — the mechanical version of watching jax_log_compiles
        (analysis/retrace.py).

        Every call is noted on the load ledger (``analysis.retrace.LEDGER
        .fit_calls``): entry and return on ``time.perf_counter()``, the
        steps dispatched and the ledger's totals at entry — what the
        calls before a long run cost, and where to cut the ledger at one.

        Offload overflow-detection lag: without ``persist_dir`` the loop
        reaches no natural join point, so an HBM-cache insert overflow
        surfaces only at the final ``finish()`` — construct the
        ShardedOffloadedTable with ``overflow_check_every_n_batches=N``
        to bound detection to N steps (one amortized device read per N).

        Ingest stall accounting: the loop is ingest-aware — each step's
        window refill (``next(batches)`` on the host critical path) is
        timed and recorded via ``observability.record_ingest_stall``
        (``ingest_stall_ms`` histogram + ``ingest_stall`` timer), so a
        data source that cannot keep step rate shows up as a measured
        per-step stall instead of an unexplained eps drop. Sources that
        account their own waits (``data.stream.ShardStream``, marked
        ``ingest_accounted``) are not double-counted — detected through
        ANY iterator wrapper (``itertools.chain``/``islice`` hide the
        marker attribute, so the loop also skips its own record
        whenever the refill's ``next()`` calls recorded ingest-stall
        entries themselves); the pre-loop window prime is warmup and
        never recorded. The identity-keyed
        lookahead contract holds for any iterator that yields each
        batch object once (generators and ``ShardStream`` both do) —
        see :meth:`train_step`.

        ``persist_dir``: incremental-persist offloaded tables whenever they
        signal ``should_persist`` — the reference's AutoPersist callback
        (test/benchmark/criteo_deepctr.py:113-124 polling
        should_persist_server_model each batch). Persists run on a
        background thread (``blocking=False``) so the loop keeps training
        during the commit — the update_early_return overlap
        (EmbeddingStoreOperator.cpp:42-57).

        Elastic autosave/resume (the graftproto ``delta_chain`` model's
        ``trainer_restart`` role, made real):

        * ``autosave_every=N`` with ``autosave_dir``: every N steps the
          loop takes a delta autosave of the full TrainState (embedding
          states + dense params/opt_state) into ``autosave_dir``,
          recording ``{"fit": {step, epoch, cursor}}`` in the manifest
          extra — ``cursor`` is the count of batches TRAINED so far
          (epoch-absolute; batches prefetched into the lookahead window
          but not yet stepped are deliberately NOT counted). The loop
          pays for the SNAPSHOT only (``checkpoint_delta.begin_delta``:
          the dirty sets claimed, one gather program a variable
          dispatched behind the step the save names, no wait for the
          device); the copy to the host, the files and the manifest
          rename run on one writer thread while later steps train. At
          most one save is in flight: the next snapshot waits for the
          commit before it, and ``fit`` does not return (or unwind)
          before the last is committed; a failed save re-marks its rows
          and raises from that wait. What a save holds is fixed at its
          snapshot (the model's ``trainer_step`` gated on an idle saver
          is that instant), so a kill at any sync point leaves the
          previous chain or the new one, never a mix of steps. The
          first save into an empty directory is a full save, blocking.
        * ``resume_from=DIR``: before the loop, restore TrainState from
          the newest committed version of the delta chain under DIR and
          advance ``batches`` to the recorded cursor —
          ``skip_batches(cursor)`` when the source supports exact
          positioning (``data.stream.ShardStream``), else ``cursor``
          plain ``next()`` discards (identical semantics for any
          deterministic iterator). A missing or never-armed DIR starts
          fresh at cursor 0, so the same invocation works for launch
          and every relaunch. Because the restore only ever resumes
          from a COMMITTED autosave boundary and the batch sequence is
          deterministic, a killed-and-resumed fit trains bit-identically
          to an uninterrupted one from that boundary.

        Autosave/resume cover the jitted TrainState only; offloaded
        tables persist through their own ``persist_dir`` lane, so
        combining ``autosave_every`` with ``offload`` is refused.
        """
        if autosave_every:
            if not autosave_dir:
                raise ValueError(
                    "fit(autosave_every=) requires autosave_dir=")
            if self.offload:
                raise ValueError(
                    "fit autosave covers the jitted TrainState only; "
                    "offloaded tables persist via persist_dir= — don't "
                    "combine autosave_every with offload")
        if resume_from is not None and self.offload:
            raise ValueError(
                "fit(resume_from=) does not restore offloaded tables; "
                "restore them via their own persist lane first")
        call = LEDGER.fit_began()
        try:
            return self._fit(state, batches, call, log_every, log_fn,
                             persist_dir, retrace_budget, autosave_every,
                             autosave_dir, resume_from)
        finally:
            LEDGER.fit_returned(call)

    def _fit(self, state, batches, call, log_every, log_fn, persist_dir,
             retrace_budget, autosave_every, autosave_dir, resume_from):
        """The loop of :meth:`fit`; ``call`` is its line of the ledger."""
        last = None
        it = iter(batches)
        base_cursor = 0
        self._join_autosave()
        if resume_from is not None:
            state, base_cursor = self._restore_fit(state, resume_from)
            if base_cursor:
                skip = getattr(batches, "skip_batches", None)
                if skip is not None:
                    skip(base_cursor)
                else:
                    for k in range(base_cursor):
                        if next(it, None) is None:
                            raise ValueError(
                                f"resume cursor {base_cursor} is past "
                                f"the batch source (exhausted after "
                                f"{k}) — wrong source for this "
                                "checkpoint?")
        # a source that records its own ring waits (ShardStream) must
        # not have the same stall counted twice by the loop's timer;
        # the attribute is only the fast path — a wrapped stream
        # (itertools.chain/islice) hides it, so each refill ALSO
        # checks whether its next() calls recorded their own entries
        self_accounted = bool(
            getattr(batches, "ingest_accounted", False)
            or getattr(it, "ingest_accounted", False))
        # the lookahead window holds the NEXT pipeline_depth batches; the
        # head of the window is the batch about to step
        window: deque = deque()

        def refill() -> float:
            t0 = time.perf_counter()
            while len(window) <= self.pipeline_depth:
                nxt = next(it, None)
                if nxt is None:
                    break
                window.append(nxt)
            return time.perf_counter() - t0

        refill()   # window prime: warmup, deliberately unrecorded
        i = 0
        guard = None
        # the step a save names, kept on the host: reading state.step at
        # a save would wait for every step in flight
        step0 = int(jax.device_get(state.step)) if autosave_every else 0
        try:
            while window:
                # prepare the whole window through the chain — head
                # included, so the apply always finds its batch at the
                # front of the prep queue; during step N the preps for
                # N+1..N+K are the ones genuinely in flight
                if self.offload:
                    for b in window:
                        if not self._prep_started(b):
                            self._start_host_prepare(b)
                batch = window.popleft()
                pops0 = (None if self_accounted
                         else observability.ingest_stall_records())
                with scope.span("trainer.next_batch"):
                    stall_s = refill()
                if not self_accounted \
                        and observability.ingest_stall_records() == pops0:
                    observability.record_ingest_stall(stall_s)
                # one step of the delta_chain model's trainer_step
                # action — the chaos injection site for "kill the
                # trainer between any two steps"
                sync_point("trainer.fit.step")
                state, metrics = self.train_step(
                    state, batch,
                    next_batch=window[0] if window else None)
                last = metrics
                if retrace_budget is not None and guard is None and i >= 1:
                    # two-step warmup: step 1 compiles the step program,
                    # step 2 may recompile once more (its input is step
                    # 1's OUTPUT, whose shardings/layouts can differ
                    # from the init-time state); steady state starts at
                    # step 3
                    guard = RetraceGuard(budget=retrace_budget,
                                         name="Trainer.fit steady state")
                    guard.__enter__()
                if persist_dir:
                    for name, table in self.offload.items():
                        if table.should_persist:
                            info = table.persist(state.emb[name],
                                                 f"{persist_dir}/{name}",
                                                 blocking=False)
                            if log_every:
                                log_fn(f"persisted {name}: {info}")
                if autosave_every and (i + 1) % autosave_every == 0:
                    self._autosave_fit(state, autosave_dir,
                                       base_cursor + i + 1,
                                       step0 + i + 1)
                if log_every and (i + 1) % log_every == 0:
                    log_fn(
                        f"step {i + 1}: loss={float(metrics['loss']):.5f}")
                i += 1
        except BaseException as e:
            # an exception mid-loop must not mask the pipeline's deferred
            # errors NOR leave the lookahead/persister threads unjoined —
            # drain everything, suppressing secondary failures (the
            # original exception is the story)
            if guard is not None:
                guard.__exit__(type(e), e, None)
            self._drain_suppressed()
            raise
        finally:
            call.steps = i      # dispatched, whether the loop ended or broke
        # the guard covers the LOOP only: the drain below may legitimately
        # compile (a remainder-sized final flush chunk) and must not count
        # against the steady-state budget. A budget trip raises — but the
        # pipeline still gets drained (suppressed secondaries) first.
        if guard is not None:
            try:
                guard.__exit__(None, None, None)
            except BaseException:
                self._drain_suppressed()
                raise
        # drain the pipeline: the LAST batch's deferred overflow counter and
        # any in-flight background persist must raise HERE, not be lost
        self._cancel_preps()
        for table in self.offload.values():
            table.finish()
        try:
            self._join_autosave()
        except BaseException:
            self._drain_suppressed()
            raise
        return state, last

    def _restore_fit(self, state: TrainState, path: str):
        """Restore (TrainState, ingest cursor) from the delta chain at
        ``path`` — fit's ``resume_from`` leg. Commitment is manifest-
        gated, exactly like the model's ``trainer_restore`` guard: no
        manifest means nothing was ever committed (fresh launch, or a
        kill mid-full-save before the arm), and the caller's fresh
        state at cursor 0 is the correct — bit-identical — restart. A
        torn delta TAIL resumes one autosave earlier (the verified
        tail's extra); a damaged chain MIDDLE raises."""
        from . import checkpoint as ckpt_mod
        from . import checkpoint_delta as cd
        # an in-process restart (tests, notebook relaunch) may race the
        # previous fit's background compactor — join it first; loads
        # from a fresh process rely on the base_id retry instead
        cd.join_compactor(path)
        if cd.read_manifest(path) is None:
            sync_point("trainer.resume.restore")
            return state, 0
        info: Dict[str, Any] = {}
        states, dense = ckpt_mod.load_checkpoint(
            path, self.collection,
            dense_state_template=(state.params, state.opt_state),
            info=info)
        params, opt_state = dense
        fit_extra = (info.get("resume_extra") or {}).get("fit") or {}
        step = int(fit_extra.get("step", 0))
        cursor = int(fit_extra.get("cursor", 0))
        sync_point("trainer.resume.restore")
        return state.replace(step=jnp.asarray(step, jnp.int32),
                             params=params, opt_state=opt_state,
                             emb=states), cursor

    def _autosave_fit(self, state: TrainState, path: str,
                      cursor: int, step: int) -> None:
        """One delta autosave of the full TrainState as it stands after
        step ``step``, with the elastic-resume extra ``{"fit": {step,
        epoch, cursor}}`` in the manifest. ``cursor`` is epoch-absolute
        (it spans epochs of the deterministic batch sequence), so
        ``epoch`` is informational. Returns once the snapshot is
        dispatched; the rest runs on the writer thread. The span is what
        a save costs the step loop: the wait for the save before (at
        most one is in flight), the dirty sets' snapshot, the dispatch.
        The first save into an empty dir is a forced full (no manifest
        yet), blocking — the extra rides the manifest either way."""
        from . import checkpoint_delta as cd
        extra = {"fit": {"step": int(step), "epoch": 0,
                         "cursor": int(cursor)}}
        with scope.span("trainer.autosave", detail={"step": int(step)}):
            self._join_autosave()
            pending = cd.begin_delta(
                path, self.collection, state.emb,
                dense_state=(state.params, state.opt_state),
                step=int(step), extra=extra)
            if isinstance(pending, cd.PendingDelta):
                self._autosave = _BackgroundSave(pending)

    def _join_autosave(self) -> None:
        """Wait for the autosave in flight, if any, and raise its error."""
        saver, self._autosave = self._autosave, None
        if saver is not None:
            saver.join()

    def _drain_suppressed(self) -> None:
        """Unwind-path drain: join lookahead/persister threads and flush
        every offload table, suppressing secondary failures (the caller
        is already raising the story)."""
        try:
            self._cancel_preps()
        except Exception:  # noqa: BLE001 — unwinding
            pass
        try:
            self._join_autosave()
        except Exception:  # noqa: BLE001 — unwinding
            pass
        for table in self.offload.values():
            try:
                table.finish()
            except Exception:  # noqa: BLE001 — unwinding
                pass

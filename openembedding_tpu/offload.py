"""Host-offloaded embedding tier: tables bigger than HBM, cached on device.

TPU-native redesign of the reference's Persistent-Memory tier (SURVEY §2.6
PMem rows; /root/reference/openembedding/variable/PmemEmbeddingTable.h,
PmemEmbeddingItemPool.h, PmemEmbeddingOptimizerVariable.h — the ICDE'23
design): bulk rows live in cheap/slow storage (there: Optane PMem; here:
host DRAM), a bounded fast cache holds the working set (there: DRAM LRU
cache; here: an HBM open-addressing table), and checkpoints are
**incremental** via a per-row work_id watermark.

Protocol mapping:

* ``prepare(ids)``  ≈ the PMem pull's pre-touch (PmemEmbeddingOptimizer-
  Variable.h:93-122): host gathers rows absent from the device cache and
  inserts them (weights + optimizer state) before the step.
* ``pull`` / ``apply_gradients`` run entirely against the HBM cache — the
  hot path touches no host memory, like the reference's cache-hit path.
* ``flush()``       ≈ LRU eviction + pmem_flush (PmemEmbeddingTable.h:
  237-270): live cache rows are written back to host and stamped with the
  current ``work_id``; the cache is cleared (state returns on next prepare).
* ``next_work()``   ≈ per-update-batch work_id advance (:285-295).
* ``should_persist`` ≈ the reference's signal that a checkpoint is cheap/
  due (PmemEmbeddingOptimizerVariable.h:84-86): here, cache occupancy
  crossing a threshold or a full persist_pending_window of batches.
* ``persist(dir)``  ≈ lightweight incremental checkpoint: first persist
  writes a base file; later persists write only rows with
  ``work_id > last persisted watermark`` (the checkpoint-commit protocol of
  PmemEmbeddingTable.h:297-328 without the transactional pool, since host
  DRAM + files replace libpmemobj).
* ``restore(dir)``  ≈ load_pmem_pool (:191-201): base + increments replayed
  newest-wins.

Two more public calls of :class:`ShardedOffloadedTable`, for a table whose
rows are known before training starts: ``load_rows(ids, weights,
slot_rows)`` writes rows (and their optimizer slots) into the host store
by id range or id array (``restore`` goes through it), and ``warm(cache,
ids)`` makes a set of ids cache-resident in bulk chunks, the books updated
as a step's ``apply_prepared`` updates them. Inserts update the cache in
place (the table operands are donated): use the state a call returns.

**A keyed tier.** :class:`ShardedOffloadedTable` built WITHOUT a ``vocab``
holds unbounded 64-bit keys (the reference's own key path,
``to_hash_bucket_fast(col, 2**62)``, which is what its PMem server stores):
the host store is addressed by key through an index on the host
(``offload_keys.py``), store rows are handed out in order of first sight
and the store grows a block at a time, the cache is a wide-key hash table,
and a key no store has seen is born in the step as an all-in-HBM hash
table makes it, its trained row reaching the store at the next write-back.
Prepare, eviction, flush, persist and restore are the bounded tier's, by
store row; only the device boundary (insert, write-back read) and the
files speak in keys. A table fed the same column (a ``:linear`` twin) is
made with ``companion()`` and shares the index: one walk a column a step.
The class's docstring has the protocol.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys
import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .analysis import scope
from .analysis.concurrency import make_rlock, sync_point
from .dirty import DirtyTracker
from .embedding import EmbeddingSpec
from .meta import EmbeddingVariableMeta
from .optim.initializers import make_initializer
from .optim.optimizers import make_optimizer
from .utils import fs
from . import hash_table as hash_lib
from . import offload_keys as keys_lib
from . import table as table_lib

OFFLOAD_META_FILE = "offload_meta"
COMPACT_CHAIN_LEN = 8   # rebase the incremental chain past this many entries
STEP_CHUNK = 1 << 16    # most keys one between-steps insert call takes
BULK_CHUNK = 1 << 21    # keys per call of a bulk insert (warm, eviction)
WRITEBACK_CHUNK = 1 << 17       # rows one write-back read fetches
WRITEBACK_ASYNC_ROWS = 1 << 22  # most rows read ahead of the writer thread


def _pow2_ceil(n: int) -> int:
    return 1 << max(5, (max(int(n), 1) - 1).bit_length())


def _persist_store(path: str, *, vocab: int, meta: EmbeddingVariableMeta,
                   work_id: int, persisted_work: int,
                   host_weights: np.ndarray,
                   host_slots: Dict[str, np.ndarray],
                   host_work_id: np.ndarray,
                   compress: str = "",
                   keys: Optional[Any] = None,
                   stored: Optional[np.ndarray] = None) -> Dict[str, Any]:
    """Shared base/delta checkpoint writer (both offload tiers).

    First call writes a base file with every row; later calls write only
    rows whose watermark moved past ``persisted_work`` — the reference's
    incremental-commit protocol (PmemEmbeddingTable.h:297-328). Like the
    reference's periodic rebase, the chain is COMPACTED once it exceeds
    ``COMPACT_CHAIN_LEN`` entries: a fresh base replaces the whole chain and
    superseded files are deleted, bounding file count, meta size, and
    restore replay time over arbitrarily long runs.

    A keyed store (``keys``: the key of each store row; ``stored``: the
    rows that hold something) writes its rows under their KEYS in ``ids``,
    the base every stored row; a bounded store's base says ``row_range``
    and lists no id.

    The commit is CRASH-CONSISTENT (the transactional property of the
    reference's checkpoint list in the pool root,
    PmemEmbeddingItemPool.h:236-296): the chain file and the meta are each
    written tmp + fsync + atomic-rename (``fs.open_atomic``), and the meta
    rename is the single commit point. A kill at ANY instant leaves either
    the previous chain (new file is an orphan, GC'd on restore) or the new
    chain (stale files are orphans, GC'd on restore) — never a meta that
    references a torn or missing file.
    """
    fs.makedirs(path)
    meta_path = fs.join(path, OFFLOAD_META_FILE)
    chain = []
    if fs.exists(meta_path):
        chain = fs.read_json(meta_path)["checkpoints"]
    # GC runs on the WRITE path only: the persisting process owns this
    # directory (one table = one dir, single writer), so sweeping here can
    # never race another writer's in-flight files — a restore-side sweep
    # could delete a live writer's just-renamed chain file or tmp
    _gc_orphans(path, chain)
    if len(chain) >= COMPACT_CHAIN_LEN:
        stale = [e["file"] for e in chain]
        chain = []
    else:
        stale = []
    # compress="zlib" writes deflate npz members (np.savez_compressed);
    # np.load reads both forms, so raw and compressed entries can share
    # one chain and restore needs no changes (the message_compress knob
    # applied to this plane's cold storage, client/EnvConfig.cpp:27-34)
    from .utils import compress as compress_lib
    savez = np.savez_compressed \
        if compress_lib.check_persist_codec(compress) else np.savez
    base = not chain
    fname = f"base_{work_id}.npz" if base else f"inc_{work_id}.npz"
    if base and keys is None:
        # every row, said by its range: the ids would be vocab x 8 bytes
        # of 0..vocab-1 (1.3 GB at 163.6M rows)
        named = {"row_range": np.asarray([0, vocab], np.int64)}
        rows, changed = slice(None), vocab
    else:
        if keys is None:
            take = host_work_id > persisted_work
        else:
            take = stored if base else stored & (
                np.asarray(host_work_id)[:stored.size] > persisted_work)
        rows = np.nonzero(take)[0]
        named = {"ids": rows.astype(np.int64) if keys is None
                 else keys[rows]}
        changed = int(rows.size)
    with fs.open_atomic(fs.join(path, fname)) as f:
        savez(f, weights=host_weights[rows], work_id=host_work_id[rows],
              **named,
              **{f"slot_{k}": v[rows] for k, v in host_slots.items()})
    chain.append({"file": fname, "work_id": work_id})
    # the commit point: before this rename readers see the old chain
    fs.write_json_atomic(meta_path, {"checkpoints": chain, "vocab": vocab,
                                     "meta": meta.to_json()})
    for old in stale:
        try:
            fs.remove(fs.join(path, old))
        except OSError:
            pass
    return {"file": fname, "rows": changed}


def _gc_orphans(path: str, chain) -> int:
    """Remove chain files the committed meta does not reference (plus
    leftover ``*.tmp.<pid>`` writes) — the debris of a kill between the
    chain-file write and the meta commit, or between the meta commit and
    the stale-file sweep. Called at the start of ``_persist_store`` (the
    directory's single writer) so debris never accumulates and the sweep
    never races an in-flight write."""
    live = {e["file"] for e in chain} | {OFFLOAD_META_FILE}
    n = 0
    try:
        names = fs.listdir(path)
    except OSError:  # pragma: no cover — listing is best-effort
        return 0
    for fname in names:
        orphan_chain = (fname.endswith(".npz")
                        and (fname.startswith("base_")
                             or fname.startswith("inc_"))
                        and fname not in live)
        if orphan_chain or fs.is_tmp_orphan(fname):
            try:
                fs.remove(fs.join(path, fname))
                n += 1
            except OSError:  # pragma: no cover
                pass
    return n


def _store_rows(table, ids, weights, slot_rows, work_id) -> None:
    """Write rows (and the optimizer slots given) into ``table``'s host
    store at ``ids`` (a slice or an id array), stamped ``work_id``."""
    table.host_weights[ids] = weights
    for sname in table.host_slots:
        if sname in slot_rows:
            table.host_slots[sname][ids] = slot_rows[sname]
    table.host_work_id[ids] = work_id


def _replay_store(path: str, *, vocab: int, load) -> int:
    """Shared restore: replay base + increments (newest wins by order)
    through ``load(ids, weights, slot_rows, work_id)``, the store's row
    loader. Returns the highest persisted work id. Orphan files newer than the
    committed meta (the debris of a kill mid-persist) are simply IGNORED —
    only the meta's chain is ever read; the next persist (the directory's
    single writer) garbage-collects them."""
    meta = fs.read_json(fs.join(path, OFFLOAD_META_FILE))
    if int(meta["vocab"]) != vocab:
        raise ValueError(f"offload checkpoint vocab {meta['vocab']} != "
                         f"table vocab {vocab}")
    max_work = 0
    for entry in meta["checkpoints"]:
        data = np.load(fs.open_file(fs.join(path, entry["file"]), "rb"))
        ids = data["ids"] if "ids" in data.files \
            else slice(*(int(r) for r in data["row_range"]))
        load(ids, data["weights"],
             {k[len("slot_"):]: data[k] for k in data.files
              if k.startswith("slot_")}, data["work_id"])
        max_work = max(max_work, int(entry["work_id"]))
    return max_work


class HostOffloadedTable:
    """One embedding variable: host-resident rows + HBM hash cache.

    Single-program (replicated) device cache; the sharded variant composes
    this with the mesh exactly like sharded_hash does for plain hash tables.
    """

    def __init__(self, meta: EmbeddingVariableMeta, optimizer: Any,
                 initializer: Any = None, *,
                 vocab: int,
                 cache_capacity: int,
                 persist_pending_window: int = 64,
                 occupancy_threshold: float = 0.7,
                 seed: int = 0):
        self.meta = meta
        self.optimizer = make_optimizer(optimizer)
        self.initializer = make_initializer(
            initializer or table_lib.DEFAULT_INITIALIZER)
        self.vocab = int(vocab)
        self.cache_capacity = int(cache_capacity)
        self.persist_pending_window = persist_pending_window
        self.occupancy_threshold = occupancy_threshold
        dim = meta.embedding_dim
        dtype = np.dtype(table_lib.resolve_dtype(meta))

        # host store, eagerly initialized (the array-table contract)
        rng = jax.random.PRNGKey(seed)
        # .copy(): np.asarray over a jax buffer is a read-only view
        self.host_weights = np.asarray(
            self.initializer.init(rng, (self.vocab, dim), dtype)).copy()
        self.host_slots: Dict[str, np.ndarray] = {}
        for sname, sshape in self.optimizer.slot_shapes(dim).items():
            sdtype = np.dtype(self.optimizer.slot_dtype(sname, dtype))
            self.host_slots[sname] = np.full(
                (self.vocab,) + sshape, self.optimizer.slot_init(sname),
                dtype=sdtype)
        self.host_work_id = np.zeros(self.vocab, np.int64)

        self.work_id = 1            # current update-batch watermark
        self.persisted_work = 0     # highest watermark on disk
        self._batches_since_persist = 0
        self.cache = hash_lib.create_hash_table(
            meta, self.optimizer, capacity=self.cache_capacity,
            rng=jax.random.fold_in(rng, 1))

    # --- cache management ---------------------------------------------------
    def _cached_mask(self, ids: np.ndarray) -> np.ndarray:
        slots = hash_lib.find_rows(self.cache.keys, jnp.asarray(ids))
        return np.asarray(slots) >= 0

    def prepare(self, ids) -> None:
        """Ensure all (unique) batch ids are cache-resident (the pre-touch).

        Flushes first if the incoming rows would overflow the probe window's
        comfortable load factor.
        """
        ids = np.unique(np.asarray(ids).ravel())
        ids = ids[(ids >= 0) & (ids < self.vocab)]
        missing = ids[~self._cached_mask(ids)]
        used = int(self.cache.num_used())
        if used + missing.size > self.occupancy_threshold * self.cache_capacity:
            self.flush()
            missing = ids  # cache is empty now; re-insert the whole batch
        if missing.size == 0:
            return
        rows = self.host_weights[missing]
        srows = {k: v[missing] for k, v in self.host_slots.items()}
        self.cache = hash_lib.insert_rows(
            self.cache, jnp.asarray(missing), jnp.asarray(rows),
            {k: jnp.asarray(v) for k, v in srows.items()})
        if int(self.cache.insert_failures) > 0:
            raise RuntimeError(
                "HBM cache insert overflow — cache_capacity too small for "
                "one batch's working set")

    def pull(self, ids) -> jnp.ndarray:
        """Cache-resident lookup (call prepare(ids) first)."""
        return hash_lib.pull(self.cache, jnp.asarray(ids), None)

    def apply_gradients(self, ids, grads) -> None:
        """Cache-resident update; advances the work counter.

        Ids outside [0, vocab) are masked to the EMPTY sentinel (dropped):
        an out-of-range id written into the cache would alias or overflow a
        valid host row at flush() time.
        """
        ids = jnp.asarray(ids)
        # range-check BEFORE any dtype narrowing: a wide id must not wrap
        # into the valid range and alias a real row
        valid = (ids >= 0) & (ids < self.vocab)
        ids = jnp.where(valid, ids, 0).astype(self.cache.keys.dtype)
        ids = jnp.where(valid, ids, hash_lib.empty_key(ids.dtype))
        self.cache = hash_lib.apply_gradients(
            self.cache, self.optimizer, self.initializer, ids, grads)
        self.next_work()

    def next_work(self) -> None:
        self.work_id += 1
        self._batches_since_persist += 1

    # --- writeback / persistence -------------------------------------------
    def flush(self) -> int:
        """Write all live cache rows back to host, stamped with work_id."""
        keys = np.asarray(jax.device_get(self.cache.keys))
        live = keys != hash_lib.empty_key(keys.dtype)
        ids = keys[live]
        if ids.size:
            weights = np.asarray(jax.device_get(self.cache.weights))[live]
            self.host_weights[ids] = weights
            for sname, sval in self.cache.slots.items():
                self.host_slots[sname][ids] = np.asarray(
                    jax.device_get(sval))[live]
            self.host_work_id[ids] = self.work_id
        self.clear_cache()
        return int(ids.size)

    def clear_cache(self) -> None:
        """Drop all cache rows WITHOUT writeback (restore path)."""
        self.cache = self.cache.replace(
            keys=jnp.full_like(
                self.cache.keys,
                hash_lib.empty_key(np.dtype(self.cache.keys.dtype))),
            insert_failures=jnp.zeros((), jnp.int32))

    @property
    def should_persist(self) -> bool:
        """Cheap-checkpoint signal (reference exb_should_persist)."""
        used = int(self.cache.num_used())
        return (self._batches_since_persist >= self.persist_pending_window
                or used >= self.occupancy_threshold * self.cache_capacity)

    def persist(self, path: str) -> Dict[str, Any]:
        """Incremental checkpoint: base on first call, deltas afterwards."""
        self.flush()
        out = _persist_store(
            path, vocab=self.vocab, meta=self.meta, work_id=self.work_id,
            persisted_work=self.persisted_work,
            host_weights=self.host_weights, host_slots=self.host_slots,
            host_work_id=self.host_work_id)
        self.persisted_work = self.work_id
        self._batches_since_persist = 0
        return out

    def restore(self, path: str) -> None:
        """Replay base + increments (newest wins by construction)."""
        max_work = _replay_store(path, vocab=self.vocab,
                                 load=functools.partial(_store_rows, self))
        # keep the watermark monotonic for an in-place restore of a table
        # that has trained past the checkpoint
        self.work_id = max(self.work_id, max_work + 1)
        self.persisted_work = max_work
        self.clear_cache()  # stale pre-restore rows must not write back


_NO_ROWS = np.zeros(0, np.int64)


@dataclasses.dataclass
class PreparedBatch:
    """Host-side half of a prepare, produced ahead of time.

    ``host_prepare`` builds one of these on a BACKGROUND thread while the
    device executes the previous step (the reference's
    PrefetchPullWeights issuing pulls N batches ahead, exb_ops.cpp:109-205);
    ``apply_prepared`` then turns it into device inserts. ``needs_evict``
    marks a batch whose misses would overflow the cache budget — eviction
    rebuilds the cache, so that batch falls back to the synchronous path.
    ``gen`` stamps the residency GENERATION the prepare was computed
    against: eviction/restore rebuild the cache and bump the generation,
    so a stale in-flight prepare is recomputed at apply time instead of
    inserting rows the rebuild just dropped.
    """

    uniq: np.ndarray                      # store rows of the batch's
                                          # unique valid ids (a bounded
                                          # tier: the ids themselves)
    missing: np.ndarray                   # those stored and not cached
    rows: Optional[np.ndarray]            # host_weights[missing]
    slot_rows: Dict[str, np.ndarray]      # host_slots[*][missing]
    # keyed tier: rows of keys the store has nothing for yet; the step
    # itself makes their rows
    fresh: np.ndarray = dataclasses.field(default_factory=lambda: _NO_ROWS)
    needs_evict: bool = False
    gen: int = 0                          # residency generation stamp
    lookups: int = 0                      # ids the batch held, duplicates
                                          # included: sizes the insert


class ShardedOffloadedTable:
    """Mesh-sharded offload tier: host store + sharded HBM cache + Trainer.

    The industrial composition of :class:`HostOffloadedTable` with the
    device mesh (the reference's full PMem tier, PmemEmbeddingTable.h +
    PmemEmbeddingOptimizerVariable.h, per server shard):

    * the **HBM cache is an ordinary sharded hash table** (``sharded_hash``,
      owner-routed a2a plane) whose state lives wherever the caller keeps
      embedding states (e.g. ``TrainState.emb``) — the jitted train step
      pulls/updates it exactly like any hash variable, zero special-casing
      in the hot path;
    * the object itself holds only HOST state: the backing row store
      (optionally a disk-backed memmap) plus exact ``resident`` / ``dirty``
      / ``last_touch`` books. Because only :meth:`prepare` inserts and only
      eviction removes, the host knows cache membership without ever
      probing the device — the reference tracks the same facts in its DRAM
      index (PmemEmbeddingTable.h:143-163);
    * overflow evicts the **least-recently-touched batch** (default: down
      to half capacity), not the whole cache: dirty rows are written
      back, the cache is emptied in its own buffers, and the still-hot
      survivors are re-inserted in bulk (the reference's LRU eviction,
      :382-395);
    * writeback is **asynchronous** and copies what is dirty, not the
      table: the device gathers the dirty rows (``read_rows_sharded``),
      the copies are launched with ``copy_to_host_async`` and a writer
      thread scatters them into the host store while training continues
      (the VariableAsyncTask role, variable/VariableAsyncTask.h:12-78).
      ``prepare``/``persist`` join the writer before reading host rows.

    The work_id watermark + incremental base/delta persistence protocol is
    unchanged from :class:`HostOffloadedTable` (the ICDE'23 checkpoint
    design, PmemEmbeddingTable.h:285-328).

    **The key space decides the store.** With a ``vocab`` the ids are
    bounded, an id is its own store row and the cache is keyed by int32
    row ids. WITHOUT one (``vocab=None``, as ``input_dim == -1`` is a
    hash variable) the tier holds 64-bit keys, the reference's
    ``to_hash_bucket_fast(col, 2**62)`` ids: a host index maps a key to
    its store row (``offload_keys.KeyIndex``; rows are handed out in
    order of first sight), the store and every book are by store row and
    grow a block at a time (``offload_keys.BlockArray``), the cache is a
    wide-key table, and a batch column is int64 keys or ``[..., 2]``
    int32 pairs (``FusedMapper``'s wide form). A key is then one of
    three things to a prepare: cached or planned (nothing to do), stored
    and not cached (its row is copied in, as in a bounded tier), or
    never seen: it is handed a store row and booked, nothing is copied,
    and the STEP makes its row as an all-in-HBM hash table does
    (``hash_table.find_or_insert`` + the initializer's per-key row), so
    the tier's initializer is what a fresh key reads. Its trained row
    reaches the store at the next write-back. Everything by store row
    below (``uniq``, ``missing``, ``dirty_ids``) is an id in a bounded
    tier and an index row in a keyed one.
    """

    def __init__(self, name: str, meta: EmbeddingVariableMeta,
                 optimizer: Any, initializer: Any = None, *,
                 cache_capacity: int, mesh, vocab: Optional[int] = None,
                 persist_pending_window: int = 64,
                 occupancy_threshold: float = 0.7,
                 keep_fraction: float = 0.5,
                 backing_dir: Optional[str] = None,
                 persist_compress: str = "",
                 seed: int = 0,
                 overflow_check_every_n_batches: int = 0,
                 _space: Optional[keys_lib.KeySpace] = None):
        from .parallel import sharded_hash as sh
        self.name = name
        self.meta = meta
        self.mesh = mesh
        self.optimizer = make_optimizer(optimizer)
        self.initializer = make_initializer(
            initializer or table_lib.DEFAULT_INITIALIZER)
        self._optimizer_config = optimizer
        self._initializer_config = initializer
        # no vocab: an unbounded key space, a store addressed by key
        self.keyed = vocab is None or int(vocab) < 0
        self.vocab = -1 if self.keyed else int(vocab)
        self.cache_capacity = int(cache_capacity)
        self.persist_pending_window = persist_pending_window
        # bounded-lag overflow detection for loops that never reach a
        # natural join point (hand-driven steps, fit() without
        # persist_dir): every N batches note_update pays ONE blocking
        # device read of the deferred overflow counter. 0 (default)
        # keeps detection at join points only (flush/persist/restore/
        # finish/_evict — see check_overflow).
        self.overflow_check_every_n_batches = int(
            overflow_check_every_n_batches)
        self._batches_since_overflow_check = 0
        self.occupancy_threshold = occupancy_threshold
        self.keep_fraction = keep_fraction
        from .utils import compress as compress_lib
        # codec for the incremental persist chain (cold storage; deflate
        # npz members — np.load reads raw and compressed chains alike)
        self.persist_compress = compress_lib.check_persist_codec(
            persist_compress)
        self.spec = sh.make_hash_sharding_spec(
            mesh, cache_capacity, key_width=64 if self.keyed else 32)
        dim = meta.embedding_dim
        dtype = np.dtype(table_lib.resolve_dtype(meta))

        def _alloc(fname, shape, adtype, fill=None):
            if backing_dir:
                os.makedirs(backing_dir, exist_ok=True)
                arr = np.lib.format.open_memmap(
                    os.path.join(backing_dir, f"{name}_{fname}.npy"),
                    mode="w+", dtype=adtype, shape=shape)
            elif fill is not None and not fill:
                return np.zeros(shape, adtype)  # pages come when written
            else:
                arr = np.empty(shape, adtype)
            if fill is not None:
                arr[:] = fill
            return arr

        # a keyed tier's key space: its own, or the one it was made a
        # companion over (``companion``)
        self._owns_space = self.keyed and _space is None
        self._space = (_space or keys_lib.KeySpace()) if self.keyed else None
        self._made_with = dict(
            cache_capacity=cache_capacity, mesh=mesh,
            persist_pending_window=persist_pending_window,
            occupancy_threshold=occupancy_threshold,
            keep_fraction=keep_fraction, backing_dir=backing_dir,
            persist_compress=persist_compress, seed=seed,
            overflow_check_every_n_batches=overflow_check_every_n_batches)

        def _rows(fname, tail, adtype, fill=0):
            """One per-row array of the store or its books: ``vocab``
            rows, or blocks that grow with a keyed store."""
            if not self.keyed:
                return _alloc(fname, (self.vocab,) + tuple(tail), adtype,
                              fill)
            return keys_lib.BlockArray(
                tail, adtype, self._space.layout,
                lambda i, shape: _alloc(f"{fname}.{i}", shape, adtype, fill))

        # host store, eagerly initialized in bounded chunks (a table bigger
        # than HBM must not be materialized on device either)
        from .optim import initializers as init_lib
        if self.keyed:
            # a store row is written (load_rows, a write-back) before
            # anything reads it: a key's first row is the cache's to make
            self.host_weights = _rows("weights", (dim,), dtype)
        elif isinstance(self.initializer, init_lib.Constant):
            # constant init fills host-side: the chunked device path would
            # push the whole store through device transfers to compute a
            # constant. Nothing is put on the device here, not even a
            # PRNG key: a caller can place its caches first
            self.host_weights = _alloc("weights", (self.vocab, dim), dtype,
                                       fill=self.initializer.value)
        else:
            rng = jax.random.PRNGKey(seed)
            self.host_weights = _alloc("weights", (self.vocab, dim), dtype)
            chunk = max(1, (64 << 20) // max(1, dim * dtype.itemsize))
            for lo in range(0, self.vocab, chunk):
                hi = min(self.vocab, lo + chunk)
                self.host_weights[lo:hi] = np.asarray(self.initializer.init(
                    jax.random.fold_in(rng, lo), (hi - lo, dim), dtype))
        self.host_slots: Dict[str, np.ndarray] = {}
        for sname, sshape in self.optimizer.slot_shapes(dim).items():
            sdtype = np.dtype(self.optimizer.slot_dtype(sname, dtype))
            self.host_slots[sname] = _rows(
                f"slot_{sname}", tuple(sshape), sdtype,
                self.optimizer.slot_init(sname))
        self.host_work_id = _rows("work_id", (), np.int64)

        self._resident = _rows("resident", (), bool) if self.keyed \
            else np.zeros(self.vocab, bool)
        self._resident_count = 0  # kept exact; vocab-sized sums are O(GB)
        # PLANNED residency: rows an in-flight PreparedBatch will insert at
        # its apply. Lets a K-deep prepare chain compute batch N+k's misses
        # against residency-as-of-batch-N+k-1 without waiting for the
        # device applies; apply/cancel move or clear the marks, eviction
        # invalidates them wholesale via the generation bump
        self._planned = _rows("planned", (), bool) if self.keyed \
            else np.zeros(self.vocab, bool)
        self._planned_count = 0
        self._gen = 0
        # guards the residency books (_resident/_planned/counts/_gen):
        # host_prepare runs on the Trainer's lookahead thread WHILE
        # apply_prepared/_evict mutate the books on the main thread — at
        # depth K >= 2 some prepare is always mid-flight when an apply
        # lands, so the read-compute-mark cycle must be atomic against
        # the apply's planned->resident transfer and eviction's rebuild.
        # ALSO guards the _dirty marks (written by note_update/flush on
        # the step thread, read+cleared by writeback launch/eviction).
        # make_rlock: a plain RLock unless OE_REPORT_TRACE_LOCKS enables
        # the graftrace runtime detector (analysis/concurrency.py)
        self._book = make_rlock(f"offload.{self.name}.book")
        self.evictions = 0  # lifetime LRU-eviction count (observability)
        # prepares/applies redone because an eviction rebuilt residency
        # under them (the generation protocol's retry paths)
        self.gen_retries = 0
        self._last_touch = _rows("last_touch", (), np.int64) if self.keyed \
            else np.zeros(self.vocab, np.int64)
        self.work_id = 1
        self.persisted_work = 0
        self._batches_since_persist = 0
        self._writer: Optional[threading.Thread] = None
        self._writer_err: Optional[BaseException] = None
        # rows the failed writeback left stale; re-marked dirty at the
        # join (NOT by the writer thread itself — the evict path joins
        # the writer while holding _book, so a writer-side _book acquire
        # would deadlock). Written by the writer, read at join: the
        # thread join is the happens-before edge, no lock involved.
        self._writer_err_dirty: Optional[np.ndarray] = None
        # row-granular dirty book (rows_per_chunk=1: the writeback
        # scatter is row-exact); shares _book so dirty marks stay atomic
        # with the residency bookkeeping. The same DirtyTracker, at
        # chunk granularity, drives the whole-model delta checkpoints
        # (checkpoint.save_checkpoint mode="delta") — this tier is where
        # the machinery was generalized FROM (dirty.py).
        if self.keyed:
            # the key space has key -> store row and the key of each row;
            # the table's own: the rows handed out whose row its store
            # does not hold yet (UNBORN: a fresh key's row lives in the
            # cache until a write-back brings it)
            self._unborn = _rows("unborn", (), bool, fill=True)
            dirty_bits = _rows("dirty", (), bool)
            self._dirty = keys_lib.BlockDirty(
                dirty_bits, name=f"offload.{name}", lock=self._book)
            mine = [self.host_weights, self.host_work_id,
                    *self.host_slots.values(), self._resident,
                    self._planned, self._last_touch, self._unborn,
                    dirty_bits]
            with self._space._grow_lock:
                for arr in mine:    # rows a companion's keys already took
                    arr.grow(len(self._space.keys))
                self._space.arrays.extend(mine)
        else:
            self._dirty = DirtyTracker(self.vocab, rows_per_chunk=1,
                                       name=f"offload.{name}",
                                       lock=self._book)
        self._persister: Optional[threading.Thread] = None
        self._persister_err: Optional[BaseException] = None
        # latest cumulative insert_failures copy; read ONLY at join
        # points (every device read is a synchronous round trip, see
        # check_overflow)
        self._overflow_latest = None
        from .utils import observability
        observability.register_memory_source("offload", name, self)

    def memory_stats(self) -> Dict[str, float]:
        """Host-memory ledger gauges (``observability.memory_stats``):
        store bytes (weights + slots + work ids; a disk-backed memmap
        store is flagged, its pages are OS-evictable rather than
        resident), residency-book bytes, and the live row counters. Row
        counters read under ``_book``; the vocab-sized dirty scan is
        deliberately NOT performed (O(GB) at north-star vocab). A keyed
        tier adds its index and keys, and ``store_rows`` /
        ``index_load``."""
        store = self.host_weights.nbytes + self.host_work_id.nbytes \
            + sum(a.nbytes for a in self.host_slots.values())
        book = self._resident.nbytes + self._planned.nbytes \
            + self._dirty.nbytes + self._last_touch.nbytes
        with self._book:
            resident = self._resident_count
            planned = self._planned_count
            evictions = self.evictions
            keyed = {} if not self.keyed else {
                # a keyed store's own: the index, the key of every row
                # and the unborn flags; rows handed out; the index's load
                # (counted by the table the key space was made with)
                "index_bytes": float(self._index.nbytes
                                     * self._owns_space),
                "key_bytes": float(self._keys.nbytes * self._owns_space
                                   + self._unborn.nbytes),
                "store_rows": float(self._index.rows),
                "index_load": float(self._index.load)}
        return {
            **keyed,
            "store_bytes": float(store),
            "store_memmap": float(isinstance(self.host_weights, np.memmap)),
            "book_bytes": float(book),
            "resident_rows": float(resident),
            "planned_rows": float(planned),
            "cache_capacity_rows": float(self.cache_capacity),
            "evictions": float(evictions),
        }

    @property
    def _index(self) -> keys_lib.KeyIndex:
        return self._space.index

    @property
    def _keys(self) -> keys_lib.BlockArray:
        return self._space.keys

    def companion(self, name: str, meta: EmbeddingVariableMeta,
                  optimizer: Any = None, initializer: Any = None
                  ) -> "ShardedOffloadedTable":
        """A second keyed table over THIS table's keys: the ``:linear``
        twin of a fused table, fed the same column. It has its own rows,
        books and cache (its ``embedding_spec()`` goes beside this one's);
        the index, the key of every store row and where a row lives are
        shared, so a step's keys are found once for both
        (``offload_keys.KeySpace``) and a key's store row is the same in
        both. Built like this table unless told otherwise."""
        if not self.keyed:
            raise ValueError("a bounded tier's ids are their own rows: "
                             "there is no index to share")
        return ShardedOffloadedTable(
            name, meta, optimizer or self._optimizer_config,
            initializer or self._initializer_config, _space=self._space,
            **self._made_with)

    # --- spec / state creation ---------------------------------------------
    def embedding_spec(self, **kw) -> EmbeddingSpec:
        """The EmbeddingSpec to register this variable under in a
        collection: a hash table (the cache) with this table's configs.
        Any field may be overridden via ``kw`` (e.g. a companion
        ``name=.../output_dim=1`` linear spec)."""
        base = dict(
            name=self.name, input_dim=-1, output_dim=self.meta.embedding_dim,
            dtype=self.meta.datatype, optimizer=self._optimizer_config,
            initializer=self._initializer_config,
            hash_capacity=self.cache_capacity,
            # the cache's keys are the tier's ids, and must be the form
            # its own insert plane (``self.spec``) writes: int32 where
            # they are bounded store rows ([0, vocab)), wide pairs where
            # the key space is not
            key_dtype="wide" if self.keyed else "int32")
        return EmbeddingSpec(**{**base, **kw})

    def create_cache(self, rng: Optional[jax.Array] = None):
        from .parallel import sharded_hash as sh
        if rng is None:
            rng = jax.random.PRNGKey(0)
        return sh.create_sharded_hash_table(
            self.meta, self.optimizer, mesh=self.mesh, spec=self.spec,
            rng=rng)

    # --- writer thread ------------------------------------------------------
    def _join_writeback(self) -> None:
        if self._writer is not None:
            self._writer.join()
            self._writer = None
        if self._writer_err is not None:
            err, self._writer_err = self._writer_err, None
            redo, self._writer_err_dirty = self._writer_err_dirty, None
            if redo is not None:
                # updates not written: re-mark so a later flush retries
                # (over-marking rows re-dirtied meanwhile is harmless)
                with self._book:
                    self._dirty.restore(redo)
            raise RuntimeError("async writeback failed") from err

    def _start_writeback(self, cache, dirty_ids: np.ndarray) -> None:
        """Copy the ``dirty_ids`` rows (store rows, read from the cache
        under their keys) of the cache into the host store:
        the device gathers just those rows (``read_rows_sharded``, in
        calls of ``WRITEBACK_CHUNK`` keys), so what crosses to the host is
        what is dirty and not the table. The reads are dispatched here,
        ahead of whatever the caller does to the cache next; a writer
        thread fetches and scatters them while training continues. A set
        too large to hold on the device at once (an eviction after a long
        run without a flush) is written back chunk by chunk before this
        returns."""
        from .parallel import sharded_hash as sh
        self._join_writeback()
        # an async persist is READING host rows; the scatter below is the
        # only host-row writer — wait until the snapshot is on disk
        self._join_persist()
        if not dirty_ids.size:
            return
        work = self.work_id
        key_dtype = np.dtype(cache.keys.dtype)
        size = min(WRITEBACK_CHUNK, _pow2_ceil(dirty_ids.size))

        def read(lo):
            sub = dirty_ids[lo:lo + size]
            keys = self._device_keys(sub, size, key_dtype)
            out = sh.read_rows_sharded(cache, jnp.asarray(keys),
                                       mesh=self.mesh, spec=self.spec)
            for leaf in jax.tree.leaves(out):
                leaf.copy_to_host_async()
            return sub, out

        def store(sub, out):
            found, rows, srows = jax.device_get(out)
            found = np.asarray(found[:sub.size])
            # an id the cache does not hold (never inserted: out of the
            # budget's reach, or dropped by a rebuild) has nothing to write
            ids = sub[found]
            sync_point("offload.writeback.scatter")
            if ids.size:
                self.host_weights[ids] = rows[:sub.size][found]
                for sname in self.host_slots:
                    self.host_slots[sname][ids] = \
                        srows[sname][:sub.size][found]
                self.host_work_id[ids] = work
                if self.keyed:      # the store holds the key's row now
                    self._unborn[ids] = False

        starts = range(0, dirty_ids.size, size)
        if dirty_ids.size > WRITEBACK_ASYNC_ROWS:
            with scope.span("offload.writeback", table=self.name):
                ahead = read(0)
                for lo in starts:
                    now, ahead = ahead, (read(lo + size)
                                         if lo + size < dirty_ids.size
                                         else None)
                    store(*now)
            with self._book:
                self._dirty.clear_chunks(dirty_ids)
            return
        pending = [read(lo) for lo in starts]

        def _run():
            try:
                sync_point("offload.writeback.run")
                with scope.span("offload.writeback", table=self.name):
                    for sub, out in pending:
                        store(sub, out)
            except BaseException as e:  # noqa: BLE001 — re-raised at join
                # _writer_err_dirty re-marks the rows AT THE JOIN (see
                # __init__: the writer must not take _book itself)
                self._writer_err_dirty = dirty_ids
                self._writer_err = e

        # clear eagerly so updates landing DURING the writeback re-mark
        # their rows; restored at the join on failure
        with self._book:
            self._dirty.clear_chunks(dirty_ids)
        self._writer = threading.Thread(
            target=_run, daemon=True, name=f"oe-writeback-{self.name}")
        self._writer.start()

    # --- cache management ---------------------------------------------------
    def _gather_host(self, ids: np.ndarray):
        """Host-row gather for ``ids``: (weights, slot rows). Pure reads —
        safe on a background thread as long as no writeback/evict mutates
        the store meanwhile (writebacks only touch DIRTY rows, which are
        resident, and gathers only touch MISSING rows, which are not — the
        two row sets are disjoint by construction)."""
        rows = self.host_weights[ids]
        srows = {k: v[ids] for k, v in self.host_slots.items()}
        return rows, srows

    def _device_keys(self, rows: np.ndarray, size: int,
                     key_dtype: np.dtype) -> np.ndarray:
        """The cache's keys of store ``rows``, padded with EMPTY to
        ``size``: the rows themselves, or a keyed tier's ``[size, 2]``
        (lo, hi) pairs."""
        empty = hash_lib.empty_key(key_dtype)
        if self.keyed:
            keys = np.full((size, 2), empty, key_dtype)
            keys[:rows.size] = hash_lib.split64(self._keys[rows])
        else:
            keys = np.full((size,), empty, key_dtype)
            keys[:rows.size] = rows
        return keys

    def _packed_layout(self, key_dtype: np.dtype):
        """Static column layout for the one-transfer insert, or None when
        the table's dtypes rule it out (key words must be int32 and
        weights and every slot f32, so that one 32-bit buffer carries
        them all): the key's column (a keyed tier: its two), the weight
        row, then each slot's."""
        if key_dtype != np.int32 \
                or self.host_weights.dtype != np.float32 \
                or any(a.dtype != np.float32
                       for a in self.host_slots.values()):
            return None
        dim = int(np.prod(self.host_weights.shape[1:], dtype=np.int64))
        col = (2 if self.keyed else 1) + dim
        layout = []
        for sname in sorted(self.host_slots):
            shape = tuple(self.host_slots[sname].shape[1:])
            cols = int(np.prod(shape, dtype=np.int64)) if shape else 1
            layout.append((sname, col, cols, shape))
            col += cols
        return dim, col, tuple(layout)

    def _step_insert_size(self, lookups: int, misses: int) -> int:
        """Padded size of the insert between two steps. The program
        compiles once a size, and miss counts differ from step to step, so
        a table has ONE size while a step's misses fit it: the largest
        power of two in an eighth of the batch's lookups (a batch of the
        same shape gives the same size whatever it misses). A step that
        misses more (a cold cache, the batch after an eviction) takes the
        second size, ``STEP_CHUNK``, in as many calls as it needs."""
        small = min(STEP_CHUNK, max(32, 1 << (max(lookups // 8, 1)
                                              .bit_length() - 1)))
        return small if misses <= small else STEP_CHUNK

    def _insert_rows(self, cache, ids: np.ndarray, rows: np.ndarray,
                     slot_rows: Dict[str, np.ndarray], size: int):
        """Device half of an insert: pre-gathered host rows (of store
        rows ``ids``) -> HBM cache,
        in calls of ``size`` keys each (the last one padded with EMPTY
        keys, which the insert skips). The cache is updated in place: the
        program donates keys, weights and slots.

        The payload ships as ONE packed 32-bit buffer per chunk (f32 with
        the int32 key bitcast into column 0; a keyed tier's int32, both
        key words in columns 0-1 and the rows by their bits) when dtypes
        allow — the per-step transfer count is
        a measured cost on high-latency links (`python -m tools.offload_diag puts`) —
        with the generic per-array path as the fallback."""
        from .parallel import sharded_hash as sh
        key_dtype = np.dtype(cache.keys.dtype)
        packed_fmt = self._packed_layout(key_dtype)
        h2d_bytes = 0
        for lo in range(0, ids.size, size):
            sub = ids[lo:lo + size]
            # the host's own work of an insert (pack, start the copy) and
            # the program's call are timed apart: with a step running, the
            # first program call after it waits inside the runtime until
            # that step is done (PERF.md, PR 28), and that is no host work
            with scope.span("offload.insert_pack", table=self.name):
                if packed_fmt is not None:
                    dim, total_cols, layout = packed_fmt
                    first = 2 if self.keyed else 1      # key columns
                    buf = np.zeros((size, total_cols), np.float32)
                    words = buf.view(np.int32)  # the same 32 bits a cell
                    words[:, :first] = self._device_keys(
                        sub, size, np.int32).reshape(size, first)
                    buf[:sub.size, first:first + dim] = \
                        rows[lo:lo + size].reshape(sub.size, dim)
                    for sname, start, cols, _shape in layout:
                        buf[:sub.size, start:start + cols] = \
                            slot_rows[sname][lo:lo + size].reshape(
                                sub.size, cols)
                    h2d_bytes += buf.nbytes
                    # a wide table's program takes the buffer as int32:
                    # a key word is any 32 bits, a NaN's among them
                    args = (jnp.asarray(words if self.keyed else buf),
                            layout)
                else:
                    ck = self._device_keys(sub, size, key_dtype)
                    cw = np.zeros((size,) + self.host_weights.shape[1:],
                                  self.host_weights.dtype)
                    cw[:sub.size] = rows[lo:lo + size]
                    h2d_bytes += ck.nbytes + cw.nbytes
                    srows = {}
                    for sname, arr in self.host_slots.items():
                        cs = np.zeros((size,) + arr.shape[1:], arr.dtype)
                        cs[:sub.size] = slot_rows[sname][lo:lo + size]
                        h2d_bytes += cs.nbytes
                        srows[sname] = jnp.asarray(cs)
                    args = (jnp.asarray(ck), jnp.asarray(cw), srows)
            # DEFER the overflow readback: ``insert_failures`` is CUMULATIVE
            # (hash_table.py:494, psum-merged across shards,
            # sharded_hash.py:214), so the latest copy subsumes every earlier
            # one — keep exactly one independent buffer (the jitted step
            # donates the cache pytree, deleting its buffers) and read it
            # ONLY at join points (flush/persist/restore/finish). Any
            # per-step read — even of a counter copied steps earlier, even
            # with ``copy_to_host_async`` primed — costs a synchronous device
            # round trip; one per table per step serializes the tier
            # (`python -m tools.offload_diag pipeline`).
            with scope.span("offload.insert_dispatch", table=self.name):
                insert = sh.insert_rows_sharded_packed \
                    if packed_fmt is not None else sh.insert_rows_sharded
                cache = insert(cache, *args, mesh=self.mesh, spec=self.spec)
                self._overflow_latest = cache.insert_failures + jnp.int32(0)
        scope.HISTOGRAMS.inc("offload_h2d_bytes", h2d_bytes,
                             table=self.name)
        return cache

    def check_overflow(self, cache=None) -> None:
        """Read the cache's cumulative insert-overflow counter; raises
        if any insert since creation (or the last eviction rebuild, which
        checks before discarding) ever overflowed a probe window.

        This is a JOIN-POINT operation — ``flush``/``persist``/
        ``restore``/``finish``/``_evict`` — and deliberately has no
        automatic per-step counterpart: every device read is a
        synchronous round trip, and one per table per step serializes the
        whole tier (`python -m tools.offload_diag pipeline`). ``fit(persist_dir=...)``
        reaches a join every ``persist_pending_window`` batches;
        hand-driven loops at ``finish()`` — or every
        ``overflow_check_every_n_batches`` steps when that knob is set
        (``note_update`` drives it). The counter is cleared only after a
        SUCCESSFUL read, so a transient device failure does not lose the
        evidence.

        ``cache``: when the caller holds the LIVE cache state
        (flush/_evict/persist), its ``insert_failures`` counter is read
        directly — strictly more complete than the ``_overflow_latest``
        copy taken at the last host-side insert, which misses failures
        the jitted step's gradient-apply auto-insert accumulated since
        (e.g. out-of-range batch ids; see the _start_writeback guard).
        Same single device round trip either way."""
        if cache is not None:
            v = cache.insert_failures
        elif self._overflow_latest is not None:
            v = self._overflow_latest
        else:
            return
        overflowed = int(jax.device_get(v)) > 0   # may raise; keep v
        # the cumulative live counter subsumes any older copy
        self._overflow_latest = None
        self._batches_since_overflow_check = 0
        if overflowed:
            raise RuntimeError(
                f"offloaded table {self.name!r}: HBM cache insert "
                "overflow — raise cache_capacity or lower "
                "occupancy_threshold")

    def _insert_from_host(self, cache, ids: np.ndarray):
        """Bulk insert of host rows: ``BULK_CHUNK`` keys a call (one size
        for the whole set), each chunk gathered as it goes so that the
        host holds one chunk's rows at a time."""
        size = min(BULK_CHUNK, _pow2_ceil(ids.size))
        for lo in range(0, ids.size, size):
            sub = ids[lo:lo + size]
            rows, srows = self._gather_host(sub)
            cache = self._insert_rows(cache, sub, rows, srows, size)
        return cache

    def distinct(self, ids) -> np.ndarray:
        """The distinct valid ids of a batch column, sorted: what
        :meth:`host_prepare` makes of it first, so that tables fed one
        column can share the pass (``Trainer`` does). A keyed tier
        answers int64 keys, from int64 ids or ``[..., 2]`` (lo, hi)
        pairs (a trailing axis of 2 is a pair axis, the collection's own
        rule; a narrow dtype's minimum is its padding)."""
        ids = np.asarray(ids)
        if not self.keyed:
            ids = np.unique(ids.ravel())
            return ids[(ids >= 0) & (ids < self.vocab)]
        if ids.ndim >= 2 and ids.shape[-1] == 2:
            if ids.dtype == np.int32 and ids.flags.c_contiguous \
                    and sys.byteorder == "little":
                keys = np.unique(ids.view(np.int64))    # (lo, hi) as is
            else:
                keys = np.unique(hash_lib.join64(ids.astype(np.int32)))
        else:
            keys = np.unique(ids.ravel())
            if keys.dtype.itemsize < 8:
                keys = keys[keys != np.iinfo(keys.dtype).min]
            keys = keys.astype(np.int64)
        return keys[keys_lib.valid_keys(keys)]

    def lookups_of(self, ids) -> int:
        """Lookups a batch column holds, duplicates included."""
        ids = np.asarray(ids)
        pairs = self.keyed and ids.ndim >= 2 and ids.shape[-1] == 2
        return int(ids.size // 2 if pairs else ids.size)

    def rows_of(self, ids, *, insert: bool = False) -> np.ndarray:
        """Store row of each of ``ids`` (DISTINCT; a keyed tier's int64
        keys), -1 where the store has none; with ``insert`` a keyed
        tier hands such a key its row (unborn: nothing stored yet)."""
        ids = np.asarray(ids, np.int64)
        if not self.keyed:
            return np.where((ids >= 0) & (ids < self.vocab), ids, -1)
        # a companion asked about the same key array is answered from
        # the space's last answers: one walk a column a step
        with scope.span("offload.key_index", table=self.name):
            rows, _handed, probes = self._space.rows_of(
                ids, insert, lambda: scope.span("offload.store_grow",
                                                table=self.name))
        # rows handed out and the index's load are gauges of the memory
        # ledger (``memory_stats``: ``store_rows``, ``index_load``)
        if probes:
            scope.HISTOGRAMS.inc("offload_index_probes", probes,
                                 table=self.name)
        return rows

    def host_prepare(self, ids, *, lookups: Optional[int] = None,
                     distinct: bool = False) -> PreparedBatch:
        """Host-only half of :meth:`prepare`: residency math + host gather.
        (``distinct``: ``ids`` is already :meth:`distinct` of a batch
        column of ``lookups`` lookups.)

        Misses are computed against ``resident OR planned``, and the
        result's own misses are marked PLANNED before returning — so a
        chain of host_prepares for batches N+1..N+K (each run after the
        previous one finished, e.g. on the Trainer's serialized lookahead
        thread) sees exactly the residency each batch will find at its
        apply, K batches before those applies run (the reference's
        prefetch ``steps`` budget, exb_ops.cpp:109-205, attr :148-156).
        Every prepared batch MUST then reach :meth:`apply_prepared` or
        :meth:`cancel_prepared` (cancel ALL outstanding ones together —
        later prepares assume earlier ones will insert their rows).
        NOTE the pipeline's detection lag: a prepared insert that
        overflows a cache shard surfaces at the next JOIN POINT —
        ``flush``/``persist``/``restore``/``finish`` (see
        :meth:`check_overflow`; per-step reads would serialize the
        pipeline on a device round trip per table).

        A keyed tier first finds each distinct key's store row in its
        index (one walk, span ``offload.key_index``); a key the index
        has not seen is handed the next row there and comes out
        ``fresh``: planned like a miss, copied from nowhere.
        """
        if lookups is None:
            lookups = self.lookups_of(ids)
        if not distinct:
            ids = self.distinct(ids)
        rows = self.rows_of(ids, insert=True) if self.keyed else ids
        return self._prepare_rows(rows, lookups)

    def _prepare_rows(self, ids: np.ndarray, lookups: int) -> PreparedBatch:
        """:meth:`host_prepare` of a batch's distinct store rows."""
        budget = int(self.occupancy_threshold * self.cache_capacity)
        while True:
            with self._book:
                gen = self._gen
                absent = ids[~(self._resident[ids] | self._planned[ids])]
                missing, fresh = self._born_unborn(absent)
                if self._resident_count + self._planned_count \
                        + absent.size > budget:
                    # eviction rebuilds the cache (sync path); no gather
                    return PreparedBatch(uniq=ids, missing=missing,
                                         rows=None, slot_rows={},
                                         fresh=fresh, needs_evict=True,
                                         gen=gen, lookups=lookups)
            # gather OUTSIDE the lock (large memmap reads; safe — missing
            # rows are neither resident nor planned, so neither writeback
            # nor eviction touches them)
            rows, srows = self._gather_host(missing)
            with self._book:
                if self._gen != gen:
                    self._count_gen_retry()
                    continue  # evicted under the gather; recompute
                # mark AFTER the gather succeeded — a failed prepare
                # leaks nothing
                self._planned[absent] = True
                self._planned_count += int(absent.size)
            # counted here, on whichever thread prepares: the step's
            # critical path carries no counter of the tier but the bytes
            # it copies
            scope.HISTOGRAMS.inc("offload_unique_rows", ids.size,
                                 table=self.name)
            scope.HISTOGRAMS.inc("offload_miss_rows", missing.size,
                                 table=self.name)
            if fresh.size:
                scope.HISTOGRAMS.inc("offload_fresh_keys", fresh.size,
                                     table=self.name)
            return PreparedBatch(uniq=ids, missing=missing, rows=rows,
                                 slot_rows=srows, fresh=fresh, gen=gen,
                                 lookups=lookups)

    def _born_unborn(self, rows: np.ndarray):
        """``rows`` split into those the store holds a row for and those
        it does not yet (a keyed tier's fresh keys; none when bounded)."""
        if not self.keyed:
            return rows, _NO_ROWS
        unborn = self._unborn[rows]
        return rows[~unborn], rows[unborn]

    def _count_gen_retry(self) -> None:
        self.gen_retries += 1
        scope.HISTOGRAMS.inc("offload_gen_retries", table=self.name)

    def cancel_prepared(self, prep: PreparedBatch) -> None:
        """Release a prepared batch that will never be applied (the
        Trainer abandoned its lookahead window). Must be called for ALL
        outstanding prepares — each later prepare's miss set assumed the
        earlier ones' planned rows."""
        with self._book:
            if prep.gen == self._gen and not prep.needs_evict:
                for rows in (prep.missing, prep.fresh):
                    self._planned[rows] = False
                    self._planned_count -= int(rows.size)

    def apply_prepared(self, cache, prep: PreparedBatch):
        """Device half: turn a :class:`PreparedBatch` into cache inserts.
        Falls back to the synchronous evict path when the batch overflows
        the budget, and recomputes stale prepares (an eviction between
        prepare and apply rebuilt the cache). Returns the updated cache
        state; the one passed in is donated to the insert."""
        with scope.span("offload.apply_prepared", table=self.name):
            return self._apply_prepared(cache, prep)

    def _apply_prepared(self, cache, prep: PreparedBatch):
        with self._book:
            # needs_evict prepares are NOT exempt: after the first evict
            # of an overflow episode, the rest of the lookahead window's
            # evict-verdicts are stale too — recomputing gives them a
            # fresh budget check instead of K-1 redundant full rebuilds
            stale = prep.gen != self._gen
            if stale:
                # Residency was rebuilt under this prepare (eviction/
                # restore bumped the generation): recompute — same uniq,
                # fresh misses. The recompute must happen IN BATCH ORDER:
                # a later lookahead prepare may already have re-planned
                # under the new generation and claimed keys THIS batch
                # needs resident now (its own apply runs K steps too
                # late). So, atomically (the RLock is held across the
                # whole recompute+apply): drop every planned claim, bump
                # the generation again — later prepares re-recompute at
                # THEIR applies — and reclaim for this batch first.
                self._gen += 1
                self._planned[:] = False
                self._planned_count = 0
                self._count_gen_retry()
                inner = self._prepare_rows(prep.uniq, prep.lookups)
                try:
                    return self._apply_prepared(cache, inner)
                except BaseException:
                    # the INNER prep holds the live planned marks (the
                    # caller only knows the stale outer prep, whose
                    # cancel is a no-op at the old generation)
                    self.cancel_prepared(inner)
                    raise
        # join FIRST: the caller's next jitted step may donate (delete) the
        # very cache buffers an in-flight async flush is still reading
        self._join_writeback()
        # deliberately NO overflow read here: the per-step path must not
        # touch the device (each read is a synchronous round trip that
        # would re-serialize the tier); detection happens at join points
        # (see check_overflow)
        self._last_touch[prep.uniq] = self.work_id
        if prep.needs_evict:
            budget = int(self.occupancy_threshold * self.cache_capacity)
            # ONE atomic section for evict + re-derive + mark: a lookahead
            # host_prepare recomputing after the generation bump must not
            # claim (plan) keys this batch is about to insert — it would
            # re-insert them at ITS apply with pre-update host rows,
            # clobbering this step's gradient updates
            with self._book:
                cache = self._evict(cache, protect=prep.uniq,
                                    budget=budget,
                                    incoming=prep.missing.size
                                    + prep.fresh.size)
                # re-gather AFTER eviction made host rows current
                absent = prep.uniq[~self._resident[prep.uniq]]
                missing, fresh = self._born_unborn(absent)
                rows, slot_rows = self._gather_host(missing)
                self._resident[absent] = True
                self._resident_count += int(absent.size)
        else:
            missing, fresh, rows, slot_rows = prep.missing, prep.fresh, \
                prep.rows, prep.slot_rows
            absent = np.concatenate([missing, fresh]) if fresh.size \
                else missing
            with self._book:
                # transfer planned -> resident atomically: a concurrent
                # host_prepare must never observe these keys as absent
                # from both books
                self._resident[absent] = True
                self._resident_count += int(absent.size)
                self._planned[absent] = False
                self._planned_count -= int(absent.size)
        if fresh.size:
            # the step makes a fresh key's row in the cache: the store
            # has none, so the row is owed a write-back from now on
            with self._book:
                self._dirty.mark_rows(fresh)
        if missing.size == 0:
            return cache
        try:
            return self._insert_rows(
                cache, missing, rows, slot_rows,
                self._step_insert_size(prep.lookups, missing.size))
        except BaseException:
            # unwind the optimistic marks to the pre-apply state: a caller
            # that survives the error (retry loop) must not find the books
            # claiming rows the cache never received, and a RETRY of the
            # same prep must be able to re-run the planned->resident
            # transfer it came in with
            with self._book:
                self._resident[absent] = False
                self._resident_count -= int(absent.size)
                if not prep.needs_evict:
                    self._planned[absent] = True
                    self._planned_count += int(absent.size)
            raise

    def prepare(self, cache, ids):
        """Make every (unique, valid) batch id cache-resident; returns the
        updated cache state. Evicts the least-recently-touched rows first
        when the incoming set would overflow the load-factor budget.
        (The synchronous convenience composition of ``host_prepare`` +
        ``apply_prepared``.)"""
        return self.apply_prepared(cache, self.host_prepare(ids))

    def _cleared(self, cache):
        """``cache`` with every slot free, as :meth:`create_cache` makes
        one (keys EMPTY, weights 0, slots at their start), written into
        its own donated buffers."""
        empty = hash_lib.empty_key(np.dtype(cache.keys.dtype))
        starts = {k: self.optimizer.slot_init(k) for k in cache.slots}

        def cleared(keys, weights, slots):
            return (jnp.full_like(keys, empty), jnp.zeros_like(weights),
                    {k: jnp.full_like(v, starts[k])
                     for k, v in slots.items()})

        table = (cache.keys, cache.weights, cache.slots)
        # keep_unused: the fills read nothing of the operands, and an
        # operand jit drops is not donated: a second cache would be made
        keys, weights, slots = jax.jit(
            cleared, donate_argnums=(0, 1, 2), keep_unused=True,
            out_shardings=jax.tree.map(lambda a: a.sharding, table))(*table)
        return cache.replace(keys=keys, weights=weights, slots=slots,
                             insert_failures=cache.insert_failures * 0)

    def load_rows(self, ids, weights, slot_rows=None) -> None:
        """Write known rows into the host store: ``ids`` is a range
        ``slice(lo, hi)`` (one contiguous copy a call: the way to fill a
        store of 10^8 rows chunk by chunk) or an array of row ids, a
        keyed tier's an array of DISTINCT int64 keys (a key the store has
        not seen is handed its row);
        ``weights`` ``[n, dim]`` and ``slot_rows`` ``{slot: [n, ...]}``
        their rows (a slot left out keeps what it holds). The rows are
        stamped with the current ``work_id``, so the next ``persist``
        carries them. A row that is resident in the cache, or planned
        into it, would be read back stale: refused. ``restore`` replays
        its files through the same writer."""
        self._join_writeback()
        self._join_persist()
        self._write_rows(ids, weights, slot_rows or {}, self.work_id,
                         held_refused=True)

    def _write_rows(self, ids, weights, slot_rows, work_id,
                    held_refused: bool = False) -> None:
        if self.keyed:
            keys = np.asarray(ids, np.int64).ravel()
            if keys.size > 1 and not (keys[1:] > keys[:-1]).all() \
                    and np.unique(keys).size != keys.size:
                raise ValueError(
                    f"offloaded table {self.name!r}: load_rows takes each "
                    "key once")
            if not keys_lib.valid_keys(keys).all():
                raise ValueError(
                    f"offloaded table {self.name!r}: a key whose high "
                    "word is the EMPTY marker cannot be stored")
            ids = self.rows_of(keys, insert=True)
        with self._book:
            if held_refused and (self._resident[ids].any()
                                 or self._planned[ids].any()):
                raise ValueError(
                    f"offloaded table {self.name!r}: load_rows over rows "
                    "the cache holds; load before warming, or restore")
        _store_rows(self, ids, weights, slot_rows, work_id)
        if self.keyed:
            self._unborn[ids] = False

    def warm(self, cache, ids):
        """Make ``ids`` cache-resident in bulk: the state a cache is in
        between two evictions, reached in ``BULK_CHUNK`` keys a call
        instead of a step's misses at a time. The books move as
        :meth:`apply_prepared` moves them (resident marks and count, last
        touch); nothing is evicted: a set that does not fit the budget is
        refused. Returns the updated cache state."""
        ids = self.distinct(ids)
        if self.keyed:      # the keys the store holds a row for
            ids = self.rows_of(ids)
            ids = ids[ids >= 0]
            ids = ids[~self._unborn[ids]]
        self._join_writeback()
        with self._book:
            missing = ids[~(self._resident[ids] | self._planned[ids])]
            budget = int(self.occupancy_threshold * self.cache_capacity)
            if self._resident_count + self._planned_count \
                    + missing.size > budget:
                raise ValueError(
                    f"offloaded table {self.name!r}: warming "
                    f"{missing.size} rows passes the cache's budget of "
                    f"{budget}")
            self._resident[missing] = True
            self._resident_count += int(missing.size)
        self._last_touch[ids] = self.work_id
        if missing.size == 0:
            return cache
        try:
            with scope.span("offload.warm", table=self.name):
                return self._insert_from_host(cache, missing)
        except BaseException:
            with self._book:    # the books must not claim rows never sent
                self._resident[missing] = False
                self._resident_count -= int(missing.size)
            raise

    def _evict(self, cache, protect: np.ndarray, budget: int,
               incoming: int):
        """LRU-batch eviction: write back dirty rows, keep the hottest
        survivors, rebuild the cache with them (open-addressing tables
        never delete, so eviction = writeback + rebuild-from-host). The
        rebuilt cache is the old one's buffers, emptied in place: a second
        cache beside the first does not fit a chip the first fills."""
        sync_point("offload.evict")
        with scope.span("offload.evict", table=self.name):
            self._join_writeback()
            # eviction DISCARDS the cache (_cleared zeroes the
            # cumulative insert_failures) — read the pending overflow
            # evidence from the LIVE counter first (the _overflow_latest
            # copy misses failures the jitted step accumulated after the
            # last host-side insert), or an overflow between the last
            # join point and this rebuild would vanish; eviction is
            # already a synchronous join, so the device round trip costs
            # nothing extra here
            self.check_overflow(cache)
            resident_ids = np.nonzero(self._resident)[0]
            keep_target = max(0, min(int(self.keep_fraction * budget),
                                     budget - incoming))
            prot = np.zeros(len(self._resident), bool)
            prot[protect] = True
            candidates = resident_ids[~prot[resident_ids]]
            order = np.argsort(self._last_touch[candidates], kind="stable")
            keep_protected = resident_ids[prot[resident_ids]]
            n_keep = max(0, keep_target - keep_protected.size)
            keep = np.concatenate([keep_protected,
                                   candidates[order][::-1][:n_keep]])
            # writeback every dirty resident row (host becomes fully
            # current), synchronously — the rebuild below must read
            # current host rows
            dirty_ids = resident_ids[self._dirty.mask_rows(resident_ids)]
            self._start_writeback(cache, dirty_ids)
            self._join_writeback()
            if self.keyed:
                # a row still unborn was never in the cache (a fresh key
                # prepared for a pull alone): nothing to rebuild it from,
                # and its next prepare finds it fresh again
                keep = keep[~self._unborn[keep]]
            cache = self._cleared(cache)
            self._resident[:] = False
            self._resident_count = 0
            # invalidate every in-flight prepare: their miss sets were
            # computed against the residency this rebuild just dropped
            self._gen += 1
            self._planned[:] = False
            self._planned_count = 0
            self.evictions += 1
            scope.HISTOGRAMS.inc("offload_evictions", table=self.name)
            if keep.size:
                cache = self._insert_from_host(cache, np.sort(keep))
                self._resident[keep] = True
                self._resident_count = int(keep.size)
            return cache

    # --- step bookkeeping ---------------------------------------------------
    def note_update(self, ids, *, uniq: Optional[np.ndarray] = None) -> None:
        """Record that the jitted step applied gradients for ``ids``
        (host-side dirty marks + work watermark advance). ``uniq`` skips
        the np.unique (and a keyed tier's index walk) when the caller
        already holds the store rows of this batch's unique valid ids (a
        PreparedBatch carries them).

        With ``overflow_check_every_n_batches`` set, every N-th call also
        reads the deferred overflow counter (one device round trip,
        amortized over N steps) so hand-driven loops and ``fit()``
        without ``persist_dir`` detect an HBM-cache insert overflow
        within N steps instead of only at ``finish()``."""
        with scope.span("offload.note_update", table=self.name):
            if uniq is None:
                uniq = self.rows_of(self.distinct(ids))
                uniq = uniq[uniq >= 0]
            with self._book:
                self._dirty.mark_rows(uniq)
            self.work_id += 1
            self._batches_since_persist += 1
        n = self.overflow_check_every_n_batches
        if n > 0:
            self._batches_since_overflow_check += 1
            if self._batches_since_overflow_check >= n:
                self.check_overflow()

    # --- persistence --------------------------------------------------------
    def flush(self, cache) -> int:
        """Asynchronously write back all dirty rows (cache stays intact).
        Raises any error a PREVIOUS async writeback stored, even when
        nothing is dirty now (the join below would otherwise be skipped
        and a dead writer's exception would sit unread until finish)."""
        with scope.span("offload.flush", table=self.name):
            self._join_writeback()
            self.check_overflow(cache)
            sync_point("offload.flush")
            with self._book:
                dirty_ids = self._dirty.dirty_chunks()
            if dirty_ids.size:
                self._start_writeback(cache, dirty_ids)
            return int(dirty_ids.size)

    @property
    def should_persist(self) -> bool:
        return (self._batches_since_persist >= self.persist_pending_window
                or self._resident_count
                >= self.occupancy_threshold * self.cache_capacity)

    def _join_persist(self) -> None:
        if self._persister is not None:
            self._persister.join()
            self._persister = None
        if self._persister_err is not None:
            err, self._persister_err = self._persister_err, None
            raise RuntimeError("async persist failed") from err

    def finish(self) -> None:
        """End-of-loop barrier for the pipeline's loose ends: joins/raises
        the async writeback and persist (both are joined even when the
        writeback join raises, so a daemon persister is never left to die
        mid-write at interpreter exit), then raises any deferred insert
        overflow. ``Trainer.fit`` calls this before returning;
        hand-driven loops should too. The joins come FIRST — same order
        as ``flush`` — so a pending overflow raise cannot drop the stored
        writeback error or skip the failed-row dirty re-mark."""
        try:
            self._join_writeback()
        finally:
            self._join_persist()
        self.check_overflow()

    def persist(self, cache, path: str, *,
                blocking: bool = True) -> Dict[str, Any]:
        """Incremental checkpoint (base on first call, deltas afterwards).

        ``blocking=False`` runs the file write on a BACKGROUND thread so
        training continues during the commit — the reference's
        update_early_return overlap (EmbeddingStoreOperator.cpp:42-57).
        Safe because the persister only READS host rows and the only host-
        row WRITER (``_start_writeback``) joins any in-flight persist
        first; crash-consistency comes from the atomic chain/meta commits.
        Returns ``{"async": True}`` immediately in that mode; errors
        surface on the next persist/flush/restore join.
        """
        self.flush(cache)
        self._join_writeback()
        self._join_persist()
        work, persisted = self.work_id, self.persisted_work
        # watermarks advance optimistically: should_persist goes quiet now;
        # on failure the join raises and the next persist re-covers the
        # rows (their host_work_id stamps are > the last COMMITTED meta)
        self.persisted_work = self.work_id
        self._batches_since_persist = 0
        store = dict(vocab=self.vocab, meta=self.meta, work_id=work,
                     persisted_work=persisted,
                     host_weights=self.host_weights,
                     host_slots=self.host_slots,
                     host_work_id=self.host_work_id,
                     compress=self.persist_compress)
        if self.keyed:      # rows under their keys; no unborn row
            with self._book:
                store.update(keys=self._keys, stored=~np.asarray(
                    self._unborn)[:self._index.rows])
        if blocking:
            with scope.span("offload.persist", table=self.name):
                return _persist_store(path, **store)

        def _run():
            try:
                with scope.span("offload.persist", table=self.name):
                    _persist_store(path, **store)
            except BaseException as e:  # noqa: BLE001 — re-raised at join
                self._persister_err = e
                self.persisted_work = persisted

        self._persister = threading.Thread(
            target=_run, daemon=True, name=f"oe-persist-{self.name}")
        self._persister.start()
        return {"async": True, "work_id": work}

    def restore(self, path: str):
        """Replay base + increments into the host store; returns a FRESH
        empty cache state (pre-restore cache rows must not write back).

        RAISES on pending pre-restore overflow (a behavior change from
        the earlier API, which silently cleared it): training before this
        restore may have run on initializer rows for the failed keys, and
        the same ``cache_capacity`` would overflow again after it — wrap
        restore in the same RuntimeError handling as ``flush``/
        ``finish`` if you use it as a recovery path.

        A keyed tier's cache makes a fresh key's row from its
        ``init_rng``: ``replace(init_rng=...)`` on the returned state
        keeps the key the discarded cache drew under."""
        self._join_writeback()
        self._join_persist()
        # surface any overflow the discarded cache accumulated — training
        # before this restore may have run against initializer rows, and
        # the same cache_capacity would overflow again after it
        self.check_overflow()
        if self.keyed:
            # the store is what the files hold and nothing else: a key
            # born after them has no row again (its place in the index
            # stays: a companion may hold it). A new tier's index is
            # built as the files' keys arrive
            with self._book:
                self._unborn[:] = True
        max_work = _replay_store(path, vocab=self.vocab,
                                 load=self._write_rows)
        self.work_id = max(self.work_id, max_work + 1)
        self.persisted_work = max_work
        self._batches_since_persist = 0
        with self._book:
            self._resident[:] = False
            self._resident_count = 0
            self._gen += 1
            self._planned[:] = False
            self._planned_count = 0
            self._dirty.clear_all()
            self._last_touch[:] = 0
        return self.create_cache(jax.random.PRNGKey(int(self.work_id)))

"""Chunk-level dirty tracking for incremental (delta) checkpoints.

Generalization of the offload tier's ``_book``/``_dirty`` machinery
(``offload.ShardedOffloadedTable``): one reusable bitmap that ARRAY
tables, HASH tables, and their co-indexed optimizer slots all feed, so
``checkpoint.save_checkpoint(mode="delta")`` can write only the chunks
that changed since the last save — the reference's ICDE'23 incremental
checkpoints from dirty tracking (PmemEmbeddingTable.h:285-328), lifted
out of the PMem tier into the whole-model checkpoint plane.

Granularity: an array table is tracked to the ROW wherever one flag a
row fits (:data:`MAX_ROW_FLAGS`): hashed ids land everywhere, so a step's
~35k distinct rows dirty every one of a thousand contiguous chunks of an
81.8M-row table and a chunked "delta" is the whole table; to the row it
is the 4% that changed (PERF.md, PR 32). :class:`RowTracker` also keeps
the fresh marks in arrival order, so a snapshot costs by the rows
marked, not by a scan of the table's flags. Callers that pass
``target_chunks`` keep chunks of contiguous rows (a delta file is then a
run of row ranges) and ``key % n`` chunks of a hash table; without it a
hash table is tracked to the KEY (:class:`KeyTracker`: the same hashed
keys land in every ``key % n`` chunk too). The offload tier uses
``rows_per_chunk=1`` over its own book.

Mapping:

* array tables: logical row id -> chunk ``id // rows_per_chunk``
  (:meth:`DirtyTracker.mark_rows`); a delta chunk is the contiguous
  logical range ``[c * R, min((c+1) * R, vocab))``.
* hash tables: the 64-bit key itself (:class:`KeyTracker`); with
  ``target_chunks``, 64-bit key -> chunk ``key % num_chunks``
  (:meth:`DirtyTracker.mark_keys`), a delta chunk being the set of live
  keys whose joined 64-bit value falls in it. Stable across key-width
  migrations (the owner rule uses the same joined value).
* optimizer slots are co-indexed with their weights — the same chunk
  marks cover them; a delta writes weights AND slots for dirty chunks.

Thread discipline (graftrace): marks land from the Trainer's step loop
while a delta save's snapshot/clear runs on the caller (or a writer
joins/restores on failure) — every bitmap access goes through one lock.
``lock=`` lets an owner with an existing book (the offload ``_book``
RLock) share it so its dirty marks stay atomic with its residency
bookkeeping, exactly as before the refactor.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .analysis.concurrency import make_lock, sync_point
from .utils.hashing import mix64


class DirtyTracker:
    """Chunk-granular dirty bitmap with an exact dirty count.

    All methods are thread-safe under the tracker's lock (or the shared
    lock passed at construction). Over-marking is always safe — a chunk
    marked dirty that did not change costs delta bytes, never
    correctness — so producers may mark conservatively (e.g. every batch
    id, including ids whose gradient was zero).
    """

    def __init__(self, num_chunks: int, *, rows_per_chunk: int = 1,
                 name: str = "", lock=None):
        if num_chunks <= 0:
            raise ValueError(f"num_chunks must be positive, got {num_chunks}")
        if rows_per_chunk <= 0:
            raise ValueError(
                f"rows_per_chunk must be positive, got {rows_per_chunk}")
        self.num_chunks = int(num_chunks)
        self.rows_per_chunk = int(rows_per_chunk)
        self.name = name
        self._bits = np.zeros(self.num_chunks, bool)
        self._count = 0
        # make_lock: plain Lock unless OE_REPORT_TRACE_LOCKS arms the
        # graftrace runtime detector (analysis/concurrency.py). A shared
        # lock may be an RLock (offload passes its _book) — only ``with``
        # acquire/release is used, so either kind works.
        self._lock = lock if lock is not None \
            else make_lock(f"dirty.{name or 'tracker'}")

    # --- mapping -----------------------------------------------------------
    def chunks_of_rows(self, ids) -> np.ndarray:
        """Chunk index for each logical row id (out-of-range ids are the
        caller's concern; :meth:`mark_chunks` drops them)."""
        ids = np.asarray(ids, np.int64).ravel()
        if self.rows_per_chunk == 1:
            return ids
        return ids // self.rows_per_chunk

    def chunks_of_keys(self, keys64) -> np.ndarray:
        """Chunk index for 64-bit hash keys: nonnegative ``key % n``
        (numpy's mod of a negative int by a positive is nonnegative, so
        negative keys land in a valid chunk)."""
        keys = np.asarray(keys64, np.int64).ravel()
        return keys % np.int64(self.num_chunks)

    def chunk_row_range(self, chunk: int, vocab: int):
        """Logical row range ``[lo, hi)`` of one array-table chunk."""
        lo = int(chunk) * self.rows_per_chunk
        return lo, min(lo + self.rows_per_chunk, int(vocab))

    # --- marking -----------------------------------------------------------
    def mark_rows(self, ids) -> None:
        self.mark_chunks(self.chunks_of_rows(ids))

    def mark_keys(self, keys64) -> None:
        self.mark_chunks(self.chunks_of_keys(keys64))

    def mark_chunks(self, chunks) -> None:
        chunks = np.asarray(chunks, np.int64).ravel()
        chunks = chunks[(chunks >= 0) & (chunks < self.num_chunks)]
        if not chunks.size:
            return
        # interleaving marker OUTSIDE the lock: a gated test parks the
        # marking thread here without wedging the bitmap for others
        # (graftproto dirty_tracker model action `mark`)
        sync_point("dirty.mark")
        with self._lock:
            fresh = chunks[~self._bits[chunks]]
            if fresh.size:
                fresh = np.unique(fresh)
                self._bits[fresh] = True
                self._count += int(fresh.size)
                self._note_fresh(fresh)

    def _note_fresh(self, fresh: np.ndarray) -> None:
        """Under the lock: chunks a mark has just set (distinct)."""

    def mark_all(self) -> None:
        with self._lock:
            self._bits[:] = True
            self._count = self.num_chunks

    # --- clearing / snapshots ----------------------------------------------
    def clear_chunks(self, chunks) -> None:
        chunks = np.asarray(chunks, np.int64).ravel()
        chunks = chunks[(chunks >= 0) & (chunks < self.num_chunks)]
        if not chunks.size:
            return
        with self._lock:
            set_ = chunks[self._bits[chunks]]
            if set_.size:
                set_ = np.unique(set_)
                self._bits[set_] = False
                self._count -= int(set_.size)

    def clear_all(self) -> None:
        with self._lock:
            self._bits[:] = False
            self._count = 0

    def dirty_chunks(self) -> np.ndarray:
        """Sorted dirty chunk ids (a snapshot; bits stay set)."""
        with self._lock:
            return np.nonzero(self._bits)[0]

    def snapshot_clear(self) -> np.ndarray:
        """Atomically take the dirty set and clear it — the delta writer's
        claim. On a FAILED write the caller must :meth:`restore` the
        snapshot so the next save re-covers those chunks (marks landing
        during the failed write are preserved either way: clearing is
        exact-set, not wholesale)."""
        with self._lock:
            chunks = np.nonzero(self._bits)[0]
            self._bits[:] = False
            self._count = 0
        sync_point("dirty.snapshot")
        return chunks

    def restore(self, chunks) -> None:
        """Re-mark a failed writer's snapshot (over-marking chunks that
        were re-dirtied meanwhile is harmless)."""
        sync_point("dirty.restore")
        self.mark_chunks(chunks)

    def mask_chunks(self, chunks) -> np.ndarray:
        """Dirty bit for each chunk index (out-of-range reads as clean)."""
        chunks = np.asarray(chunks, np.int64).ravel()
        ok = (chunks >= 0) & (chunks < self.num_chunks)
        out = np.zeros(chunks.shape, bool)
        with self._lock:
            out[ok] = self._bits[chunks[ok]]
        return out

    def mask_rows(self, ids) -> np.ndarray:
        return self.mask_chunks(self.chunks_of_rows(ids))

    def __getitem__(self, ids):
        """Row-indexed dirty read — the pre-refactor ``_dirty[ids]``
        bitmap syntax the offload tier (and its tests) used."""
        out = self.mask_rows(ids)
        if isinstance(ids, (int, np.integer)):
            return bool(out[0])
        return out

    # --- introspection -----------------------------------------------------
    @property
    def dirty_count(self) -> int:
        with self._lock:
            return self._count

    @property
    def nbytes(self) -> int:
        """Bitmap bytes (graftwatch host-memory ledger)."""
        return int(self._bits.nbytes)

    def __repr__(self) -> str:  # pragma: no cover — debugging aid
        return (f"DirtyTracker({self.name!r}, chunks={self.num_chunks}, "
                f"rows_per_chunk={self.rows_per_chunk}, "
                f"dirty={self.dirty_count})")


# One flag a row up to here (2 GiB of flags); a larger table is tracked in
# chunks of the fewest contiguous rows that keep the flags under it.
MAX_ROW_FLAGS = 1 << 31
_LOG_START = 1 << 16


class RowTracker(DirtyTracker):
    """Row-exact tracker of an array table that also logs its fresh marks.

    A mark already finds the rows that were clean (it counts them), so it
    appends them to a log; :meth:`snapshot_clear` hands that log out
    instead of scanning ``num_chunks`` flags: at 81.8M rows the scan is
    0.1-0.3 s on the step thread, the log is there already. The snapshot
    is in ARRIVAL order, each row once. ``mark_all`` / ``clear_chunks``
    (nothing on the save path calls them) drop the log, and the next
    snapshot scans and starts a new one.
    """

    def __init__(self, vocab: int, *, name: str = "", lock=None):
        super().__init__(vocab, rows_per_chunk=1, name=name, lock=lock)
        self._log: Optional[np.ndarray] = np.empty(_LOG_START, np.int64)
        self._logged = 0

    def _note_fresh(self, fresh: np.ndarray) -> None:
        if self._log is None:
            return
        end = self._logged + fresh.size
        if end > self._log.size:
            grown = np.empty(max(end, 2 * self._log.size), np.int64)
            grown[:self._logged] = self._log[:self._logged]
            self._log = grown
        self._log[self._logged:end] = fresh
        self._logged = end

    def mark_all(self) -> None:
        with self._lock:
            self._bits[:] = True
            self._count = self.num_chunks
            self._log = None

    def clear_chunks(self, chunks) -> None:
        super().clear_chunks(chunks)
        with self._lock:
            self._log = None

    def clear_all(self) -> None:
        with self._lock:
            self._bits[:] = False
            self._count = 0
            self._log, self._logged = np.empty(_LOG_START, np.int64), 0

    def snapshot_clear(self) -> np.ndarray:
        with self._lock:
            if self._log is None:
                rows = np.nonzero(self._bits)[0]
                size = _LOG_START
            else:
                # the log's buffer leaves with the snapshot; the next one
                # is as large, untouched until marks fill it
                rows, size = self._log[:self._logged], self._log.size
            if rows.size * 8 < self.num_chunks:
                self._bits[rows] = False
            else:
                self._bits[:] = False
            self._count = 0
            self._log, self._logged = np.empty(size, np.int64), 0
        sync_point("dirty.snapshot")
        return rows


def make_array_tracker(name: str, vocab: int,
                       target_chunks: Optional[int] = None,
                       lock=None) -> DirtyTracker:
    """Tracker for a bounded (array) variable. The granularity comes from
    the table: to the row (:class:`RowTracker`) up to
    :data:`MAX_ROW_FLAGS` rows. ``target_chunks`` asks for ~that many
    chunks of contiguous logical rows instead (at least one row each)."""
    vocab = max(1, int(vocab))
    if target_chunks is None:
        rows = -(-vocab // MAX_ROW_FLAGS)
        if rows == 1:
            return RowTracker(vocab, name=name, lock=lock)
    else:
        rows = max(1, -(-vocab // max(1, int(target_chunks))))
    return DirtyTracker(-(-vocab // rows), rows_per_chunk=rows,
                        name=name, lock=lock)


class KeyTracker:
    """Dirty set of a hash table, exact to the key: the 64-bit keys marked
    since the last snapshot, each once, in arrival order.

    Hashed keys have no row id to flag, and ``key % n`` chunks say nothing
    about them: a step's ~35k distinct 62-bit keys land in every one of a
    thousand chunks, so a chunked delta is the whole table. The set is an
    open-addressing table of int64 on the host (linear probing, at most
    half full, never deleted from between two snapshots) beside a log of
    the keys it took in: a mark costs one ``np.unique`` of the batch and a
    few vectorized probes of its distinct keys, a snapshot hands the log
    out and wipes the table. Keys equal to the table's EMPTY sentinel
    (what padding joins to) are the caller's to drop.

    The surface a delta save uses is :class:`DirtyTracker`'s
    (``mark_keys``, ``snapshot_clear``, ``restore``, ``dirty_count``), with
    keys where that has chunk ids; the same lock and sync points.
    """

    EMPTY = np.int64(np.iinfo(np.int64).min)

    def __init__(self, *, name: str = "", lock=None):
        self.name = name
        self._tab = np.full(_LOG_START, self.EMPTY, np.int64)
        self._log = np.empty(_LOG_START, np.int64)
        self._logged = 0
        self._lock = lock if lock is not None \
            else make_lock(f"dirty.{name or 'tracker'}")

    def _place(self, keys: np.ndarray) -> np.ndarray:
        """Under the lock: put DISTINCT ``keys`` into the table; the ones
        it did not hold. Contenders for one free slot all write, one stays,
        the others go on to the next slot with the keys that met another."""
        tab, mask = self._tab, np.int64(self._tab.size - 1)
        at = (mix64(keys) & np.uint64(mask)).astype(np.int64)
        fresh = []
        while keys.size:
            cur = tab[at]
            free = cur == self.EMPTY
            if free.any():
                tab[at[free]] = keys[free]
                cur = tab[at]
                fresh.append(keys[free & (cur == keys)])
            left = cur != keys
            keys, at = keys[left], (at[left] + 1) & mask
        return np.concatenate(fresh) if fresh else keys

    def mark_keys(self, keys64, *, distinct: bool = False) -> None:
        """Mark 64-bit keys (``distinct``: the caller has made them so)."""
        keys = np.asarray(keys64, np.int64).ravel()
        if not distinct:
            keys = np.unique(keys)
        if not keys.size:
            return
        sync_point("dirty.mark")
        with self._lock:
            fresh = self._place(keys)
            end = self._logged + fresh.size
            if end > self._log.size:
                grown = np.empty(max(end, 2 * self._log.size), np.int64)
                grown[:self._logged] = self._log[:self._logged]
                self._log = grown
            self._log[self._logged:end] = fresh
            self._logged = end
            if 2 * end > self._tab.size:
                size = self._tab.size
                while 2 * end > size:
                    size *= 2
                self._tab = np.full(size, self.EMPTY, np.int64)
                self._place(self._log[:end])

    def snapshot_clear(self) -> np.ndarray:
        """Atomically take the dirty keys and clear the set: the delta
        writer's claim (a failed write :meth:`restore`s it)."""
        with self._lock:
            keys, size = self._log[:self._logged], self._log.size
            if keys.size:
                self._tab.fill(self.EMPTY)
            self._log, self._logged = np.empty(size, np.int64), 0
        sync_point("dirty.snapshot")
        return keys

    def restore(self, keys) -> None:
        """Re-mark a failed writer's snapshot."""
        sync_point("dirty.restore")
        self.mark_keys(keys)

    def dirty_keys(self) -> np.ndarray:
        """The dirty keys in arrival order (a copy; they stay marked)."""
        with self._lock:
            return self._log[:self._logged].copy()

    @property
    def dirty_count(self) -> int:
        with self._lock:
            return self._logged

    @property
    def nbytes(self) -> int:
        """Set and log bytes (graftwatch host-memory ledger)."""
        with self._lock:
            return int(self._tab.nbytes + self._log.nbytes)

    def __repr__(self) -> str:  # pragma: no cover — debugging aid
        return f"KeyTracker({self.name!r}, dirty={self.dirty_count})"


def make_hash_tracker(name: str, capacity: int,
                      target_chunks: Optional[int] = None,
                      lock=None):
    """Tracker for a hash variable: exact to the key
    (:class:`KeyTracker`). ``target_chunks`` asks for the key space
    partitioned into ``min(target_chunks, capacity)`` chunks by ``key % n``
    instead: a delta then ships every live key of a dirty chunk."""
    if target_chunks is None:
        return KeyTracker(name=name, lock=lock)
    n = max(1, min(int(target_chunks), max(1, int(capacity))))
    return DirtyTracker(n, name=name, lock=lock)

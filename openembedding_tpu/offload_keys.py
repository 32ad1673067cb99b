"""Host side of an offload tier over an UNBOUNDED key space.

A bounded tier (``ShardedOffloadedTable(vocab=N)``) needs none of this: an
id is its own store row. A tier built without a ``vocab`` holds 64-bit
keys (the reference's ``to_hash_bucket_fast(col, 2**62)`` ids), and its
host store is addressed by key:

* :class:`KeyIndex`: key -> store row, an open-addressing table over numpy
  arrays (linear probing, at most ``MAX_LOAD`` full; ``dirty.KeyTracker``'s
  scheme with a row beside each key). Rows are handed out in order of first
  sight and never taken back.
* :class:`BlockArray`: one per-row array of the store or its books, held
  as a list of blocks (:class:`BlockLayout`: doubling up to
  ``STORE_BLOCK`` rows, then that many each). Growing appends a block: nothing already stored is copied,
  and a reader on another thread keeps reading the blocks it knew.
* :class:`BlockDirty`: ``dirty.DirtyTracker`` over a :class:`BlockArray`
  of flags, so that the tier's dirty book grows with its store.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable, List

import numpy as np

from .analysis.concurrency import sync_point
from .dirty import DirtyTracker
from .utils.hashing import mix64

STORE_BLOCK = 1 << 24       # most store rows a block (read when a tier is built)
FIRST_BLOCK = 1 << 14       # rows of a store's first block
INDEX_START = 1 << 16       # slots of a new index
MAX_LOAD = 0.7              # an index past this doubles
REHASH_CHUNK = 1 << 22      # slots of the old table a pass re-places
EMPTY = np.int64(np.iinfo(np.int64).min)    # what an EMPTY pair joins to


def valid_keys(keys64: np.ndarray) -> np.ndarray:
    """Mask of the keys a wide table can hold: all but the 2^32 whose high
    word is the EMPTY marker (``hash_table``'s pair encoding)."""
    return (keys64 >> np.int64(32)) != np.int64(np.iinfo(np.int32).min)


class BlockLayout:
    """Where a store row lives, for every per-row array of one store.

    The first block holds ``first_rows`` rows and each further one as
    many as all before it, up to ``block_rows`` a block: a small store
    stays small, a large one grows by a fixed step. Blocks 0..m+1 double
    (block k >= 1 starts at ``first << (k-1)``) and together hold twice
    the cap; the rest are the cap each. A prepare asks several arrays for
    the same rows, so the last few answers of :meth:`split` are kept, by
    the identity of the row array asked about."""

    KEPT = 64       # a batch's rows come back at its apply and its note,
                    # a lookahead window of steps after its prepare

    def __init__(self, block_rows: int, first_rows: int = None):
        first_rows = min(first_rows or FIRST_BLOCK, block_rows)
        if block_rows & (block_rows - 1) or first_rows & (first_rows - 1):
            raise ValueError("block sizes must be powers of two")
        self.block_rows = int(block_rows)
        self._first = first_rows.bit_length() - 1       # log2 of block 0
        self._cap = self.block_rows.bit_length() - 1    # log2 of the cap
        self._kept: List[tuple] = []
        self._lock = threading.Lock()

    def next_block(self, rows_held: int) -> int:
        """Rows of the block after ``rows_held`` rows."""
        return min(max(rows_held, 1 << self._first), self.block_rows)

    def locate(self, rows: np.ndarray):
        """(block, offset) of each row."""
        m, cap2 = self._cap - self._first, 2 << self._cap
        geo = np.frexp((rows >> self._first).astype(np.float64))[1]
        blk = np.where(rows < cap2, geo, m + 2 + ((rows - cap2) >> self._cap))
        start = np.where(
            blk <= m + 1,
            np.where(blk == 0, 0, np.int64(1) << (self._first + np.maximum(
                blk, 1) - 1)),
            cap2 + ((blk - m - 2) << self._cap))
        return blk, rows - start

    def split(self, rows: np.ndarray):
        """[(block, positions in ``rows``, offsets in the block)], one
        entry a block that ``rows`` touches."""
        with self._lock:
            for held, groups in self._kept:
                if held is rows:
                    return groups
        blk, off = self.locate(np.asarray(rows, np.int64))
        if not blk.size:
            return []
        if blk.min() == blk.max():
            groups = [(int(blk.flat[0]), slice(None), off)]
        else:
            order = np.argsort(blk, kind="stable")
            sorted_blk = blk[order]
            cuts = np.flatnonzero(sorted_blk[1:] != sorted_blk[:-1]) + 1
            groups = [(int(sorted_blk[lo]), order[lo:hi], off[order[lo:hi]])
                      for lo, hi in zip([0, *cuts], [*cuts, blk.size])]
        if isinstance(rows, np.ndarray) and rows.size > 64:
            with self._lock:
                self._kept = [(rows, groups)] + self._kept[:self.KEPT - 1]
        return groups


class BlockArray:
    """``[rows, *tail]`` array held as blocks that are never moved, laid
    out by a :class:`BlockLayout` (one for all the arrays of a store).

    Indexed like the flat array it stands for, by an int array of rows or
    by ``[:]``; ``alloc(block_number, shape)`` makes one block, filled as
    a new row starts. A row array handed in is not to be changed in
    place afterwards (its places are remembered by its identity)."""

    def __init__(self, tail: tuple, dtype, layout: BlockLayout,
                 alloc: Callable[[int, tuple], np.ndarray]):
        self.tail = tuple(tail)
        self.dtype = np.dtype(dtype)
        self.layout = layout
        self._alloc = alloc
        self.blocks: List[np.ndarray] = []
        self._rows = 0

    def __len__(self) -> int:
        return self._rows

    @property
    def shape(self) -> tuple:
        return (len(self),) + self.tail

    @property
    def nbytes(self) -> int:
        return sum(b.nbytes for b in self.blocks)

    def grow(self, rows: int) -> None:
        """Hold at least ``rows`` rows; blocks already there stay put."""
        while self._rows < rows:
            size = self.layout.next_block(self._rows)
            self.blocks.append(self._alloc(len(self.blocks),
                                           (size,) + self.tail))
            self._rows += size

    def __getitem__(self, rows):
        if isinstance(rows, slice):
            return np.asarray(self)[rows]
        if np.ndim(rows) == 0:
            blk, off = self.layout.locate(np.asarray([rows], np.int64))
            return self.blocks[int(blk[0])][int(off[0])]
        out = np.empty(np.shape(rows) + self.tail, self.dtype)
        for b, at, off in self.layout.split(rows):
            out[at] = self.blocks[b][off]
        return out

    def __setitem__(self, rows, value) -> None:
        if isinstance(rows, slice):
            if rows != slice(None) or np.ndim(value):
                raise IndexError("a BlockArray takes [:] = scalar, or rows")
            for b in self.blocks:
                b[:] = value
            return
        if np.ndim(value) == 0:
            for b, _, off in self.layout.split(rows):
                self.blocks[b][off] = value
            return
        value = np.asarray(value)
        for b, at, off in self.layout.split(rows):
            self.blocks[b][off] = value[at]

    def __array__(self, dtype=None, copy=None):
        flat = np.concatenate(self.blocks) if self.blocks \
            else np.empty((0,) + self.tail, self.dtype)
        return flat if dtype is None else flat.astype(dtype)


class BlockDirty(DirtyTracker):
    """:class:`DirtyTracker`, a flag a store row, over flags that grow."""

    def __init__(self, bits: BlockArray, *, name: str = "", lock=None):
        self._bits = bits
        super().__init__(1, rows_per_chunk=1, name=name, lock=lock)
        self._bits = bits       # the base class made flags of its own

    @property
    def num_chunks(self) -> int:
        return max(1, len(self._bits))

    @num_chunks.setter
    def num_chunks(self, _):     # the base class's constructor sets it
        pass

    def mark_rows(self, ids) -> None:
        """Mark DISTINCT store rows the store has handed out (what a
        prepared batch carries): no bounds to check, nothing to sort."""
        sync_point("dirty.mark")
        with self._lock:
            fresh = ids[~self._bits[ids]]
            if fresh.size:
                self._bits[fresh] = True
                self._count += int(fresh.size)


class KeySpace:
    """What the tables over one key column share: the index, the key of
    every store row, and where a row lives (one :class:`BlockLayout` for
    all their per-row arrays, so that a row array located for one table
    is located for the others). A table and its companions
    (``ShardedOffloadedTable.companion``: a ``:linear`` twin) are fed the
    same keys, so a step walks the index ONCE: the last answers of
    :meth:`rows_of` are kept by the identity of the key array asked
    about. A key's store row is the same in every table of the space;
    whether a table's store holds anything there yet is that table's own
    book."""

    KEPT = 4

    def __init__(self):
        self.index = KeyIndex()
        self.layout = BlockLayout(STORE_BLOCK)
        self.keys = BlockArray((), np.int64, self.layout,
                               lambda _, shape: np.zeros(shape, np.int64))
        self.arrays: List[BlockArray] = [self.keys]
        self.lock = threading.Lock()        # the index and the answers
        self._grow_lock = threading.Lock()
        self._answers: List[tuple] = []

    def grow(self, rows: int, growing=contextlib.nullcontext) -> None:
        """Blocks for ``rows`` store rows in every array of every table
        over the space (inside ``growing()`` where any is short); nothing
        stored moves. Asked under the lock: a table's thread must not
        go on while another's is still appending its blocks."""
        with self._grow_lock:
            if any(len(arr) < rows for arr in self.arrays):
                with growing():
                    for arr in self.arrays:
                        arr.grow(rows)

    def rows_of(self, keys: np.ndarray, insert: bool,
                growing=contextlib.nullcontext):
        """(store rows of DISTINCT ``keys``, -1 where none; rows handed
        out now; slots probed). ``insert`` hands a key the index has not
        seen its row, the arrays grown first for as many as there may
        be (under the space's lock and no table's book: a new block's
        fill holds up no step)."""
        with self.lock:
            for held, inserted, rows in self._answers:
                if held is keys and (inserted or not insert):
                    return rows, 0, 0
            before, probes = self.index.rows, self.index.probes
            if insert:
                self.grow(before + keys.size, growing)
                rows = self.index.find_or_insert(keys)
                new = rows >= before
                self.keys[rows[new]] = keys[new]
            else:
                rows = self.index.find(keys)
            self._answers = [(keys, insert, rows)] \
                + self._answers[:self.KEPT - 1]
            return rows, self.index.rows - before, self.index.probes - probes


class KeyIndex:
    """64-bit key -> store row, on the host.

    ``find`` answers -1 for a key it does not hold; ``find_or_insert``
    hands such a key the next store row. Not thread-safe: the tier calls
    both under its book lock. ``probes`` counts the slots either has
    looked at."""

    def __init__(self):
        self._keys = np.full(INDEX_START, EMPTY, np.int64)
        self._rows = np.zeros(INDEX_START, np.int32)
        self.rows = 0           # store rows handed out
        self.probes = 0

    def __len__(self) -> int:
        return self.rows

    @property
    def slots(self) -> int:
        return self._keys.size

    @property
    def load(self) -> float:
        return self.rows / self._keys.size

    @property
    def nbytes(self) -> int:
        return int(self._keys.nbytes + self._rows.nbytes)

    def _starts(self, keys: np.ndarray) -> np.ndarray:
        return (mix64(keys) & np.uint64(self._keys.size - 1)) \
            .astype(np.int64)

    def find(self, keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, np.int64)
        out = np.full(keys.shape, -1, np.int64)
        tab, rows, mask = self._keys, self._rows, self._keys.size - 1
        idx, at = np.arange(keys.size), self._starts(keys)
        while idx.size:
            self.probes += idx.size
            cur = tab[at]
            hit = cur == keys
            out[idx[hit]] = rows[at[hit]]
            on = ~hit & (cur != EMPTY)
            idx, keys, at = idx[on], keys[on], (at[on] + 1) & mask
        return out

    def _place(self, keys: np.ndarray, rows: np.ndarray) -> None:
        """Put DISTINCT keys the table does not hold at their rows.
        Contenders for one free slot all write, one stays; the others go
        on to the next slot with the keys that met another's."""
        tab, mask = self._keys, self._keys.size - 1
        at = self._starts(keys)
        while keys.size:
            self.probes += keys.size
            free = tab[at] == EMPTY
            tab[at[free]] = keys[free]
            won = free & (tab[at] == keys)
            self._rows[at[won]] = rows[won]
            on = ~won
            keys, rows, at = keys[on], rows[on], (at[on] + 1) & mask

    def _reserve(self, end: int) -> None:
        """Room for ``end`` rows under ``MAX_LOAD``: the table doubles and
        its keys are placed again, a chunk of the old table at a time."""
        if end > np.iinfo(np.int32).max:
            raise OverflowError("a keyed store holds under 2^31 rows")
        if end <= MAX_LOAD * self._keys.size:
            return
        size = self._keys.size
        while end > MAX_LOAD * size:
            size *= 2
        old_keys, old_rows = self._keys, self._rows
        self._keys = np.full(size, EMPTY, np.int64)
        self._rows = np.zeros(size, np.int32)
        for lo in range(0, old_keys.size, REHASH_CHUNK):
            cut = slice(lo, lo + REHASH_CHUNK)
            held = old_keys[cut] != EMPTY
            self._place(old_keys[cut][held], old_rows[cut][held])

    def find_or_insert(self, keys: np.ndarray) -> np.ndarray:
        """Store rows of DISTINCT ``keys``; a key not held is handed the
        next row, in the order given (rows from the old ``self.rows`` up
        are the new ones). One walk finds and places."""
        keys = np.asarray(keys, np.int64)
        self._reserve(self.rows + keys.size)
        out = np.full(keys.shape, -1, np.int64)
        slot = np.zeros(keys.shape, np.int64)
        tab, rows, mask = self._keys, self._rows, self._keys.size - 1
        idx, at = np.arange(keys.size), self._starts(keys)
        while idx.size:
            self.probes += idx.size
            cur = tab[at]
            hit = cur == keys
            out[idx[hit]] = rows[at[hit]]
            free = cur == EMPTY
            tab[at[free]] = keys[free]
            won = free & (tab[at] == keys)
            slot[idx[won]] = at[won]
            on = ~hit & ~won
            idx, keys, at = idx[on], keys[on], (at[on] + 1) & mask
        new = np.nonzero(out < 0)[0]
        out[new] = self.rows + np.arange(new.size)
        rows[slot[new]] = out[new]
        self.rows += int(new.size)
        return out

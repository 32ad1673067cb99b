"""The checkpoint's plain reference for hash tables: replay a delta chain
by key with numpy alone.

Independent of the code under test: it imports nothing of the program and
reads the files from their documented layout (README, "Incremental
checkpoints").

``<dir>/var_<vid>_<name>.d/<field>.npy``   the base: one array a field
    (``keys``, ``weights``, ``slot_<name>``), a row a live key, the same
    order in every field. ``keys`` is int32/int64 ``[n]`` or, for a
    wide-key table, int32 ``[n, 2]`` (low word, high word) of the 64-bit
    key
``<dir>/delta_manifest``                   JSON; ``chain`` lists the
    committed entries in order, each with ``seq``, ``step``, ``rows`` and
    ``vars``: {variable: {``file``, ``rows``, ``keys_exact``, ...}}
``<dir>/delta_<seq>_<vid>.npz``            one entry's keys of one
    variable: ``keys`` in the base's key form and one member a field, a
    row a key, in the order the keys were first pushed since the entry
    before; each key once

Replay is newest-wins BY KEY: the base, then every entry's rows written
over it in chain order; a key the base lacks is added. A field is held
whole on the host, one at a time, and compared with the live table, which
is read block by block over its slots (a slot whose key is the EMPTY
sentinel holds nothing).
"""

import os

import numpy as np

from .reference_chain import _vid_of, fields, manifest, variables

KEYS = "keys"


def keys64(keys):
    """int64 of keys in either form: ``[n]`` integers, or ``[n, 2]``
    int32 (low, high) words."""
    keys = np.asarray(keys)
    if keys.ndim == 1:
        return keys.astype(np.int64)
    low = keys[:, 0].view(np.uint32).astype(np.int64)
    return (keys[:, 1].astype(np.int64) << np.int64(32)) | low


def is_live(keys):
    """Slots of a live key array that hold a key: the sentinel is the key
    dtype's lowest value (in the high word of a wide key)."""
    keys = np.asarray(keys)
    word = keys[:, 1] if keys.ndim == 2 else keys
    return word != np.iinfo(keys.dtype).min


def _records(path, vid, entries=None):
    chain = manifest(path)["chain"]
    for entry in chain[:len(chain) if entries is None else entries]:
        for record in entry["vars"].values():
            if _vid_of(record) == vid:
                yield record


class _Sorted:
    """A set of distinct int64 keys with each key's position."""

    def __init__(self, keys):
        self.order = np.argsort(keys, kind="stable")
        self.sorted = keys[self.order]

    def at(self, keys):
        """Position of each key, -1 where the set lacks it (the needles
        are looked up in their own order: a binary search a key over tens
        of millions is a cache miss a level otherwise)."""
        out = np.full(keys.shape, -1, np.int64)
        if not self.sorted.size or not keys.size:
            return out
        mine = np.argsort(keys, kind="stable")
        needles = keys[mine]
        pos = np.minimum(np.searchsorted(self.sorted, needles),
                         self.sorted.size - 1)
        hit = self.sorted[pos] == needles
        out[mine[hit]] = self.order[pos[hit]]
        return out


class Index:
    """Every key the base and the chain's first ``entries`` entries hold
    of one variable: the base's rows first, then the keys an entry added,
    in the order they came. ``at(keys)`` is each key's row, -1 where the
    replay lacks it."""

    def __init__(self, path, vid, entries=None):
        self._base = _Sorted(keys64(np.load(os.path.join(
            path, variables(path)[vid], f"{KEYS}.npy"))))
        self.base_rows = self._base.sorted.size
        added = np.zeros(0, np.int64)
        self.new_keys = []      # keys each entry brought to the chain
        for record in _records(path, vid, entries):
            with np.load(os.path.join(path, record["file"])) as payload:
                keys = keys64(payload[KEYS])
            new = keys[(self._base.at(keys) < 0) & ~np.isin(keys, added)]
            self.new_keys.append(int(new.size))
            added = np.concatenate([added, new])
        self._added = _Sorted(added)
        self.rows = self.base_rows + added.size

    def at(self, keys):
        row = self._base.at(keys)
        late = self._added.at(keys)
        return np.where(late >= 0, late + self.base_rows, row)


def replayed(path, vid, field, index, entries=None):
    """One field of one variable as the base and the chain's first
    ``entries`` entries leave it, a row a key of ``index``."""
    base = np.load(os.path.join(path, variables(path)[vid],
                                f"{field}.npy"), mmap_mode="r")
    rows = np.zeros((index.rows,) + base.shape[1:], base.dtype)
    rows[:index.base_rows] = base
    for record in _records(path, vid, entries):
        with np.load(os.path.join(path, record["file"])) as payload:
            rows[index.at(keys64(payload[KEYS]))] = payload[field]
    return rows


def entry_keys(path, first=0):
    """[{variable id: keys}] of each committed entry from the chain's
    ``first`` on, as its file holds them (not as the manifest says)."""
    out = []
    for entry in manifest(path)["chain"][first:]:
        counts = {}
        for record in entry["vars"].values():
            with np.load(os.path.join(path, record["file"])) as payload:
                counts[_vid_of(record)] = int(payload[KEYS].shape[0])
        out.append(counts)
    return out


def compare(path, live, slots, entries=None, block=1 << 22):
    """The replayed chain against the live table, over every variable and
    field of the base. ``live(vid, field, lo, hi)`` returns the live
    table's slots ``[lo, hi)`` of that field (``keys`` among them) as a
    host array, ``slots`` is the table's slot count.

    ``mismatch_rows``  keys both hold whose weights or any accumulator
                       differ in any bit
    ``missing_keys``   live keys the replay lacks
    ``extra_keys``     replayed keys the live table lacks
    ``new_keys``       per entry and variable, the keys that no earlier
                       entry and not the base held
    """
    out = {"mismatch_rows": 0, "missing_keys": 0, "extra_keys": 0,
           "new_keys": {}}
    for vid in sorted(variables(path)):
        index = Index(path, vid, entries)
        out["new_keys"][vid] = index.new_keys
        where, row = [], []     # live slots that hold a key, and its row
        for lo in range(0, slots, block):
            hi = min(lo + block, slots)
            keys = live(vid, KEYS, lo, hi)
            held = np.nonzero(is_live(keys))[0]
            where.append(held + lo)
            row.append(index.at(keys64(keys[held])))
        where, row = np.concatenate(where), np.concatenate(row)
        known = row >= 0
        out["missing_keys"] += int((~known).sum())
        out["extra_keys"] += index.rows - int(np.unique(row[known]).size)
        where, row = where[known], row[known]
        differs = np.zeros(where.size, bool)
        for field in fields(path, vid):
            if field == KEYS:
                continue
            want = replayed(path, vid, field, index, entries)[row]
            for lo in range(0, slots, block):
                a, b = np.searchsorted(where, [lo, min(lo + block, slots)])
                if a < b:       # ``where`` ascends: a block is a run of it
                    got = live(vid, field, lo,
                               min(lo + block, slots))[where[a:b] - lo]
                    differs[a:b] |= _differ(want[a:b], got)
            del want
        out["mismatch_rows"] += int(differs.sum())
    return out


def _differ(a, b):
    """Per row: two equal-shaped arrays differ in any bit."""
    word = np.uint32 if a.dtype.itemsize % 4 == 0 else np.uint8
    a = np.ascontiguousarray(a).view(word).reshape(a.shape[0], -1)
    b = np.ascontiguousarray(b).view(word).reshape(b.shape[0], -1)
    return (a != b).any(axis=1)


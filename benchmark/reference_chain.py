"""The checkpoint's plain reference: replay a delta chain with numpy alone.

Independent of the code under test: it imports nothing of the program and
reads the files from their documented layout (README, "Incremental
checkpoints").

``<dir>/var_<vid>_<name>.d/<field>.npy``   the base: one array a field
    (``weights``, ``slot_<name>``), every logical row in id order
``<dir>/delta_manifest``                   JSON; ``chain`` lists the
    committed entries in order, each with ``seq``, ``step``, ``rows`` and
    ``vars``: {variable: {``file``, ``rows``, ...}}
``<dir>/delta_<seq>_<vid>.npz``            one entry's rows of one
    variable: ``chunks`` (ids), ``rows_per_chunk``, ``vocab``, and one
    member a field, the rows of chunk after chunk in the order of
    ``chunks`` (a chunk is the id range ``[c * R, min((c + 1) * R, vocab))``;
    ``R`` 1 makes a chunk a row)

Replay is newest-wins: the base, then every entry's rows written over it in
chain order. A field is held whole on the host, one at a time (2.9 GB for
the cell's widest), and compared with the live table block by block.
"""

import json
import os
import re

import numpy as np

MANIFEST = "delta_manifest"
_VAR_DIR = re.compile(r"^var_(\d+)_(.*)\.d$")


def manifest(path):
    with open(os.path.join(path, MANIFEST)) as f:
        return json.load(f)


def variables(path):
    """{variable id: its base directory's name}."""
    found = {}
    for entry in os.listdir(path):
        match = _VAR_DIR.match(entry)
        if match:
            found[int(match.group(1))] = entry
    return found


def fields(path, vid):
    """The fields the base holds of one variable."""
    return sorted(f[:-4] for f in os.listdir(
        os.path.join(path, variables(path)[vid])) if f.endswith(".npy"))


def base_bytes(path):
    """Bytes of the base's field files, every variable."""
    return sum(os.path.getsize(os.path.join(path, directory, f))
               for directory in variables(path).values()
               for f in os.listdir(os.path.join(path, directory))
               if f.endswith(".npy"))


def _vid_of(record):
    return int(record["file"].rsplit("_", 1)[1].split(".")[0])


def payload_ids(payload):
    """Logical row id of every payload row, in payload order."""
    chunks = np.asarray(payload["chunks"], np.int64)
    per, vocab = int(payload["rows_per_chunk"]), int(payload["vocab"])
    if per == 1:
        return chunks
    ids = (chunks[:, None] * per + np.arange(per, dtype=np.int64)).ravel()
    return ids[ids < vocab]


def replayed(path, vid, field, entries=None):
    """One field of one variable as the base and the chain's first
    ``entries`` entries (all of them by default) leave it: [rows, ...]."""
    rows = np.array(np.load(os.path.join(path, variables(path)[vid],
                                         f"{field}.npy"), mmap_mode="r"))
    chain = manifest(path)["chain"]
    for entry in chain[:len(chain) if entries is None else entries]:
        for record in entry["vars"].values():
            if _vid_of(record) != vid:
                continue
            with np.load(os.path.join(path, record["file"])) as payload:
                rows[payload_ids(payload)] = payload[field]
    return rows


def entry_rows(path, first=0):
    """[{variable id: rows}] of each committed entry from the chain's
    ``first`` on, as its file holds them (not as the manifest says)."""
    out = []
    for entry in manifest(path)["chain"][first:]:
        counts = {}
        for record in entry["vars"].values():
            with np.load(os.path.join(path, record["file"])) as payload:
                counts[_vid_of(record)] = int(payload["weights"].shape[0])
        out.append(counts)
    return out


def differing_rows(a, b):
    """Rows of two equal-shaped arrays that differ in any bit."""
    word = np.uint32 if a.dtype.itemsize % 4 == 0 else np.uint8
    a = np.ascontiguousarray(a).view(word).reshape(a.shape[0], -1)
    b = np.ascontiguousarray(b).view(word).reshape(b.shape[0], -1)
    return int((a != b).any(axis=1).sum())


def mismatch_rows(path, live, entries=None, block=1 << 22):
    """Rows, over every variable and field of the base, in which the
    replayed chain differs from the live table in any bit. ``live(vid,
    field, lo, hi)`` returns the live table's logical rows ``[lo, hi)`` of
    that field as a host array."""
    total = 0
    for vid in sorted(variables(path)):
        for field in fields(path, vid):
            want = replayed(path, vid, field, entries)
            for lo in range(0, want.shape[0], block):
                hi = min(lo + block, want.shape[0])
                total += differing_rows(want[lo:hi],
                                        live(vid, field, lo, hi))
            del want
    return total

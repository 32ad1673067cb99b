"""Delta checkpoint plane: base snapshot + compacted dirty-chunk chain.

The reference's ICDE 2023 PMem work makes checkpoints cheap with
lightweight INCREMENTAL saves from dirty tracking
(PmemEmbeddingTable.h:285-328); the offload tier already reproduces that
protocol for its own host store (``offload._persist_store``). This
module generalizes it to the WHOLE-MODEL checkpoint
(``checkpoint.save_checkpoint(mode="delta")``):

* a FULL save (``checkpoint._save_checkpoint_impl``, parallel shard
  writers) is the BASE; it arms the chain by writing a fresh manifest
  (:func:`init_manifest`) when the collection's dirty tracking is on;
* a DELTA save writes, per variable, only what its tracker marked
  (``dirty.py``; pushes mark rows of an array table, keys of a hash
  table) — one ``delta_<seq>_<vid>.npz`` per variable,
  written by the same parallel writer pool, checksummed per block of
  rows. It has two halves. :func:`begin_delta` is the SNAPSHOT: it
  claims the dirty set and dispatches, behind whatever step was
  dispatched last, one gather program a variable that copies the dirty
  rows of the weights and of every slot array into staging buffers
  (``sharded_table.snapshot_rows_sharded``, stage ``ckpt_gather``; for
  a hash table ``sharded_hash.snapshot_keys_sharded``, which first
  finds the dirty keys' slots, stage ``ckpt_find``): nothing waits for
  the device, and steps dispatched after it may donate the tables.
  :func:`finish_delta` is everything else (the copy to the host,
  checksums, files, the manifest rename), on any thread:
  ``Trainer.fit`` runs it on a writer thread, :func:`save_delta` right
  after the snapshot;
* the MANIFEST (``delta_manifest``, atomic rename) is the single commit
  point: a kill at ANY instant leaves either the previous chain or the
  new chain — never a manifest referencing a torn file. Torn/corrupt
  FINAL entries (crc mismatch after a partial rename on a dying disk)
  are discarded whole at load; a torn MIDDLE entry fails the load (the
  chain is replayed in order — skipping the middle would corrupt);
* a background COMPACTOR folds long chains back into a new base ON DISK
  (no device involvement — folding is the same newest-wins assignment
  the replay performs, so a crash mid-compaction leaves a directory
  that still loads to the identical state) under a chain-length /
  chain-bytes budget;
* the SAME delta stream feeds serving hot-swap: :class:`Delta` payloads
  (``read_delta`` / ``encode_delta``) are applied in place by
  ``ModelRegistry.apply_delta`` — the train->serve loop the reference
  closes with TF-Serving + the HA PS, without a full-model reload.

Delta mode is LOCAL + single-process + uncompressed-base (the delta
files themselves may be compressed): remote/multi-host dumps keep the
full-save part format. A dump written with dirty tracking DISABLED
never has a manifest and loads exactly as before.
"""

from __future__ import annotations

import dataclasses
import functools
import io
import json
import os
import re
import sys
import threading
import time
import uuid
import warnings
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .analysis.concurrency import make_lock, sync_point
from .dirty import KeyTracker
from .embedding import EmbeddingCollection
from .parallel import hot_cache
from .parallel import sharded_hash as sh
from .parallel import sharded_table as st
from .utils import fs
from . import hash_table as hash_lib
from . import table as table_lib

DELTA_MANIFEST_FILE = "delta_manifest"
# Format 2 (PR 32): an array record may carry ``block_crc`` (one crc32 a
# block of ``block_rows`` payload rows) where format 1 has ``chunk_crc``
# (one a tracker chunk: a Python call a row when the tracker is exact to
# the row). A manifest is written at the lowest format that holds it, so
# a chain of chunked deltas stays format 1, byte for byte.
# Format 3 (PR 34): a hash record may be exact to the key
# (``"keys_exact": true``): its payload holds ``keys``, ``weights`` and
# ``slot_*`` of the keys pushed since the snapshot before, in the order
# they were first pushed, and neither ``chunks`` nor ``num_chunks``.
DELTA_FORMAT = 3
_BLOCKED_FORMAT = 2
_CHUNKED_FORMAT = 1
_FORMATS = (_CHUNKED_FORMAT, _BLOCKED_FORMAT, DELTA_FORMAT)
CRC_BLOCK_ROWS = 1 << 16
# staging lengths of a snapshot are few and fixed, so that the saves of a
# steady job reuse one compiled gather a variable: powers of two from here,
# and from four gather chunks on, four lengths an octave (at most a quarter
# of padding, whole chunks)
_MIN_STAGING_ROWS = 256
# compaction budget: fold the chain into a new base past either bound
COMPACT_CHAIN_LEN = 8
COMPACT_BYTES_RATIO = 0.5
_APPLY_CHUNK = 1 << 16


def _delta_fname(seq: int, vid: int) -> str:
    return f"delta_{seq:06d}_{vid}.npz"


def _seq_ok(seq: Any) -> bool:
    """True when ``seq`` is an integral number the NATIVE reader's
    json_i64 would also accept (int64 range, no bools, no NaN/inf) —
    both readers must refuse the same manifests or they recover to
    different versions (the graftfuzz divergence oracle)."""
    if isinstance(seq, bool) or not isinstance(seq, (int, float)):
        return False
    try:
        return (seq == int(seq)
                and -(2 ** 63) < int(seq) < 2 ** 63)
    except (OverflowError, ValueError):       # inf / nan
        return False


class DeltaDecodeError(ValueError):
    """Typed refusal for corrupt/garbage delta BYTES (wire frames,
    manifest records, crc-valid-but-unparseable payloads), with offset/
    field context in the message.

    One type for the whole untrusted-bytes delta surface so damage is
    distinguishable from reader bugs: the REST ``POST /models/<sign>/
    delta`` handler maps ``ValueError`` to 400 (client sent garbage —
    this subclasses it on purpose), and the graftfuzz trichotomy oracle
    counts it as a clean typed refusal, where a raw ``struct.error`` /
    ``zlib.error`` / ``KeyError`` escaping a byte parser is scored as a
    crash. Semantic refusals keep their existing types (category swap
    ``ValueError``, checksum ``RuntimeError``, torn mid-chain
    ``RuntimeError``) — this class is specifically for bytes that could
    not be decoded at all."""


# --- bounded JSON ------------------------------------------------------------

# the native reader's kMaxDepth (native/oe_serving.cc): both readers refuse
# the same nesting, and a manifest this build writes nests under ten
JSON_MAX_DEPTH = 64
_JSON_STRING = re.compile(r'"(?:[^"\\]|\\.)*"')
_JSON_BRACKET = re.compile(r"[\[{\]}]")


def _loads_bounded(text: str, what: str) -> Any:
    """``json.loads`` of UNTRUSTED text, container nesting bounded HERE
    before the parser runs. How deep the interpreter's own parser goes
    before it raises ``RecursionError`` — or whether it does — changes
    with the Python version (3.12 parses 2000 levels); the refusal must
    not."""
    depth = deepest = 0
    for m in _JSON_BRACKET.finditer(_JSON_STRING.sub("", text)):
        if m.group() in "[{":
            depth += 1
            deepest = max(deepest, depth)
        else:
            depth -= 1
    if deepest > JSON_MAX_DEPTH:
        raise DeltaDecodeError(
            f"{what}: JSON nesting depth {deepest} exceeds the limit "
            f"{JSON_MAX_DEPTH}")
    return json.loads(text)


# --- manifest ----------------------------------------------------------------

def read_manifest(path: str) -> Optional[Dict[str, Any]]:
    """The committed manifest, or None (a plain full checkpoint)."""
    mpath = fs.join(path, DELTA_MANIFEST_FILE)
    if not fs.exists(mpath):
        return None
    with fs.open_file(mpath, "rb") as f:
        manifest = _loads_bounded(f.read().decode("utf-8"),
                                  f"delta manifest at {path!r}")
    if not isinstance(manifest, dict):
        raise DeltaDecodeError(
            f"delta manifest at {path!r} is JSON "
            f"{type(manifest).__name__}, not an object")
    if manifest.get("format") not in _FORMATS \
            or isinstance(manifest.get("format"), bool):
        raise ValueError(
            f"unknown delta manifest format {manifest.get('format')!r} "
            f"at {path!r} (this build reads formats {_FORMATS})")
    return manifest


def _write_manifest(path: str, manifest: Dict[str, Any]) -> None:
    fs.write_json_atomic(fs.join(path, DELTA_MANIFEST_FILE), manifest)


def init_manifest(path: str, *, step: int, include_optimizer: bool,
                  last_seq: int = 0,
                  content_seq: Optional[int] = None,
                  extra: Optional[Dict[str, Any]] = None
                  ) -> Dict[str, Any]:
    """Arm a fresh chain over a just-written full base. ``last_seq``
    carries the version counter across a compaction AND across a full
    save over an armed dir (seqs are burned, never reused — the serving
    hot-swap version protocol needs monotonicity; a re-arm at 0 would
    make replicas ack the next real delta as stale and silently stop
    updating — graftproto ``full_save_resets_seq``).

    ``content_seq`` records the chain seq the BASE BYTES already
    reflect, so ``applied_seq`` of a chainless manifest reports the true
    version instead of 0 (a full save dumps the live state = everything
    through ``last_seq``, hence the default).

    ``extra``: caller bookkeeping recorded WITH the commit — the elastic
    resume channel (``Trainer.fit(autosave_every=)`` records its step/
    epoch/ingest cursor here; ``resume_from`` restores from whatever
    entry the load verifies). JSON-serializable dict."""
    manifest = {"format": _CHUNKED_FORMAT,
                "base_id": uuid.uuid4().hex,
                "base_step": int(step),
                "include_optimizer": bool(include_optimizer),
                "last_seq": int(last_seq),
                "content_seq": int(last_seq if content_seq is None
                                   else content_seq),
                "extra": dict(extra) if extra else {},
                "chain": []}
    _write_manifest(path, manifest)
    return manifest


def reset_chain(path: str) -> None:
    """Remove the manifest (FIRST — the atomic commit point) and GC every
    delta file. Called by a full save before it touches base files, so a
    crash mid-save can never leave a stale chain to be replayed over a
    half-new base."""
    mpath = fs.join(path, DELTA_MANIFEST_FILE)
    if fs.exists(mpath):
        fs.remove(mpath)
    _gc_orphans(path, chain=())


def chain_state(path: str) -> Dict[str, Any]:
    """Chain summary for version bookkeeping (the serving registry sets
    a loaded model's hot-swap version from ``last_seq``)."""
    manifest = read_manifest(path)
    if manifest is None:
        return {"base_id": "", "base_step": 0, "last_seq": 0,
                "content_seq": 0, "chain_len": 0, "chain_bytes": 0}
    return {"base_id": manifest["base_id"],
            "base_step": manifest["base_step"],
            "last_seq": manifest["last_seq"],
            "content_seq": int(manifest.get("content_seq", 0)),
            "chain_len": len(manifest["chain"]),
            "chain_bytes": sum(int(e.get("bytes", 0))
                               for e in manifest["chain"])}


def _gc_orphans(path: str, chain) -> int:
    """Remove delta files the committed manifest does not reference, plus
    leftover atomic-write tmps and compaction tmps — the debris of a kill
    between a delta-file rename and the manifest commit. Runs on the
    WRITE path only (the saving process owns the directory)."""
    live = set()
    for entry in chain:
        for info in entry.get("vars", {}).values():
            live.add(info["file"])
    n = 0
    try:
        names = fs.listdir(path)
    except OSError:  # pragma: no cover — listing is best-effort
        return 0
    for fname in names:
        orphan = (fname.startswith("delta_") and fname.endswith(".npz")
                  and fname not in live)
        if orphan or fs.is_tmp_orphan(fname):
            try:
                fs.remove(fs.join(path, fname))
                n += 1
            except OSError:  # pragma: no cover
                pass
        elif fname.startswith("var_") and fname.endswith(".d"):
            # a killed compaction leaves <field>.npy.compact.tmp inside
            # var dirs (each commits via atomic rename; debris is inert)
            vdir = fs.join(path, fname)
            try:
                subnames = fs.listdir(vdir)
            except OSError:  # pragma: no cover
                continue
            for sub in subnames:
                if sub.endswith(".compact.tmp") or fs.is_tmp_orphan(sub):
                    try:
                        fs.remove(fs.join(vdir, sub))
                        n += 1
                    except OSError:  # pragma: no cover
                        pass
    return n


# --- delta payloads ----------------------------------------------------------

def _field_order(payload: Dict[str, np.ndarray]) -> List[str]:
    """Deterministic field order for checksums/wire framing: id column
    first, then weights, then slots sorted by name."""
    fields = []
    for f in ("keys", "weights"):
        if f in payload:
            fields.append(f)
    fields += sorted(k for k in payload if k.startswith("slot_"))
    return fields


def _chunk_rows(chunks: np.ndarray, rows_per_chunk: int,
                vocab: int) -> np.ndarray:
    """Logical row ids of array-table chunks, chunk by chunk in the order
    given (a chunk is the contiguous range ``[c * R, min((c+1) * R,
    vocab))``); the chunks themselves where a chunk is a row."""
    chunks = np.asarray(chunks, np.int64)
    if rows_per_chunk == 1:
        return chunks
    rows = (chunks[:, None] * rows_per_chunk
            + np.arange(min(rows_per_chunk, max(int(vocab), 1)),
                        dtype=np.int64)).ravel()
    return rows[rows < vocab]


@dataclasses.dataclass
class _StagedRows:
    """One array variable's dirty rows between snapshot and commit: the
    staging buffers on the device (weights, then each slot array, rows in
    the order of ``header["chunks"]``) and the payload's header."""
    fields: List[str]
    arrays: List[Any]
    rows: int
    header: Dict[str, np.ndarray]


def _staging_rows(rows: int) -> int:
    size = max(_MIN_STAGING_ROWS, 1 << max(rows - 1, 0).bit_length())
    if size >= 4 * table_lib.APPLY_CHUNK:
        step = size // 8
        size = -(-rows // step) * step
    return size


def _staged_fields(state, include_optimizer: bool):
    """(payload field names, table arrays) a snapshot stages: the weights,
    then each slot array by name."""
    fields, arrays = ["weights"], [state.weights]
    if include_optimizer:
        for sname in sorted(state.slots):
            fields.append(f"slot_{sname}")
            arrays.append(state.slots[sname])
    return fields, arrays


def _stage_array_rows(collection, name: str, state, tracker,
                      chunks: np.ndarray, include_optimizer: bool
                      ) -> _StagedRows:
    """Dispatch the gather of one bounded variable's dirty rows: one
    program over the weights and every slot array, by row id, into
    staging buffers of one of a few fixed lengths (the only new device
    memory of a save; no slice or copy of a table array), and the
    start of their copy to the host. Returns without waiting."""
    sspec = collection.sharding_spec(name)
    vocab = int(collection.specs[name].input_dim)
    fields, arrays = _staged_fields(state, include_optimizer)
    rows = _chunk_rows(chunks, tracker.rows_per_chunk, vocab)
    if sspec.num_shards > 1:
        shard, local = sspec.shard_and_local(rows)
        rows = shard * sspec.rows_per_shard + local
    phys = np.full(_staging_rows(int(rows.size)), -1, np.int32)
    phys[:rows.size] = rows
    staged = st.snapshot_rows_sharded(
        arrays, jnp.asarray(phys), rows.size, mesh=collection.mesh,
        spec=sspec)
    for a in staged:
        a.copy_to_host_async()
    return _StagedRows(
        fields, staged, int(rows.size),
        {"chunks": np.asarray(chunks, np.int64),
         "rows_per_chunk": np.int64(tracker.rows_per_chunk),
         "vocab": np.int64(vocab)})


@dataclasses.dataclass
class _StagedKeys:
    """One hash variable's dirty keys between snapshot and commit: the
    keys on the host in the table's key form, in the order they were
    first marked, and on the device which of them the table held and the
    staging buffers (weights, then each slot array, a row a key)."""
    fields: List[str]
    arrays: List[Any]
    keys: np.ndarray
    found: Any


def _stage_hash_keys(collection, name: str, state, keys64: np.ndarray,
                     include_optimizer: bool) -> _StagedKeys:
    """Dispatch the snapshot of one hash variable's dirty keys: one
    program that finds each key's slot and gathers its row of the weights
    and of every slot array into staging buffers of one of a few fixed
    lengths (:func:`_stage_array_rows`' lengths), and the start of their
    copy to the host. The key array is read where it is; a key the table
    does not hold (marked and never inserted) comes back not found.
    Returns without waiting."""
    fields, arrays = _staged_fields(state, include_optimizer)
    key_dtype = np.dtype(state.keys.dtype)
    n = int(keys64.size)
    if not hash_lib.is_wide(state.keys):
        keys = keys64.astype(key_dtype)
    elif sys.byteorder == "little":
        # an int64 read as two int32 is its (low, high) pair: no pass
        # over 2.9M keys on the step thread (hash_table.split64's takes
        # 20-40 ms a table there)
        keys = np.ascontiguousarray(keys64).view(np.int32).reshape(n, 2)
    else:
        keys = hash_lib.split64(keys64)
    query = np.full((_staging_rows(n),) + keys.shape[1:],
                    hash_lib.empty_key(key_dtype), key_dtype)
    query[:n] = keys
    found, staged = sh.snapshot_keys_sharded(
        state.keys, arrays, jnp.asarray(query), n, mesh=collection.mesh,
        spec=collection.sharding_spec(name))
    for a in (found, *staged):
        a.copy_to_host_async()
    return _StagedKeys(fields, staged, keys, found)


def _row_crcs(payload: Dict[str, np.ndarray], bounds) -> List[int]:
    """crc32 of the payload rows ``[a, b)`` of each of ``bounds``, over
    weights then slots in field order: one call a field and range."""
    order = _field_order(payload)
    out = []
    for a, b in bounds:
        crc = 0
        for f in order:
            crc = zlib.crc32(np.ascontiguousarray(payload[f][a:b])
                             .reshape(-1).view(np.uint8), crc)
        out.append(crc)
    return out


def _chunk_bounds(chunks, rows_per_chunk: int, vocab: int):
    """Payload row range of each chunk (the last chunk may be short)."""
    off = 0
    for c in chunks:
        c = int(c)
        n = min((c + 1) * rows_per_chunk, vocab) - c * rows_per_chunk
        yield off, off + n
        off += n


def _block_bounds(rows: int, block_rows: int):
    return ((a, min(a + block_rows, rows))
            for a in range(0, rows, block_rows))


def _hash_delta_payload(state, tracker, chunks: np.ndarray,
                        include_optimizer: bool
                        ) -> Dict[str, np.ndarray]:
    """Gather one hash variable's live rows whose key chunk is dirty.
    Newest-wins replay makes over-collection safe: every live row of a
    dirty chunk ships, whether or not that specific key changed."""
    from . import checkpoint as ckpt
    targets = {"keys": state.keys, "weights": state.weights}
    if include_optimizer:
        for sname, sval in state.slots.items():
            targets[f"slot_{sname}"] = sval
    dirty = np.zeros(tracker.num_chunks, bool)
    dirty[np.asarray(chunks, np.int64)] = True
    empty = hash_lib.empty_key(np.dtype(state.keys.dtype))
    wide = hash_lib.is_wide(state.keys)
    parts: Dict[str, list] = {f: [] for f in targets}
    for blocks in ckpt._aligned_shard_blocks(targets):
        bk = blocks["keys"]
        live = (bk[:, 1] != empty) if wide else (bk != empty)
        if not live.any():
            continue
        k64 = hash_lib.join64(bk[live]) if wide \
            else bk[live].astype(np.int64)
        sel = dirty[k64 % np.int64(tracker.num_chunks)]
        if not sel.any():
            continue
        for f, block in blocks.items():
            parts[f].append(block[live][sel])
    payload = {}
    for f, arr in targets.items():
        if parts[f]:
            payload[f] = np.concatenate(parts[f])
        else:
            payload[f] = np.zeros((0,) + arr.shape[1:],
                                  np.dtype(arr.dtype))
    payload["chunks"] = np.asarray(chunks, np.int64)
    payload["num_chunks"] = np.int64(tracker.num_chunks)
    return payload


def _keys_payload(staged: _StagedKeys) -> Dict[str, np.ndarray]:
    """The payload of a hash variable tracked to the key, once its staged
    rows are on the host: the keys the table held at the snapshot, each
    with its row of every field. A marked key it did not hold has no row
    and is left out (counter ``ckpt_delta_keys_absent``): marks laid
    ahead of their push, or an insert no probe window held."""
    from .utils import observability
    n = staged.keys.shape[0]
    found = np.asarray(staged.found)[:n]
    absent = n - int(np.count_nonzero(found))
    observability.GLOBAL.add("ckpt_delta_keys_absent", float(absent))
    take = (lambda a: a[:n][found]) if absent else (lambda a: a[:n])
    payload = {"keys": take(staged.keys)}
    for f, a in zip(staged.fields, staged.arrays):
        payload[f] = take(np.asarray(a))
    return payload


class _NpzBuffer:
    """The seekable in-memory file ``np.savez`` writes a payload into,
    sized up front. Its writes are numpy copies of plain bytes, which
    release the interpreter lock; ``io.BytesIO`` grows by reallocating
    under the lock and hands its bytes out as one more copy, which for a
    276 MB payload held the lock, and the step thread of a training loop
    with it, for about a second of every save (PERF.md, PR 32)."""

    def __init__(self, capacity: int):
        self._bytes = np.empty(max(int(capacity), 1), np.uint8)
        self._at = self._end = 0

    def write(self, data) -> int:
        data = np.frombuffer(data, np.uint8)
        end = self._at + data.size
        if end > self._bytes.size:
            grown = np.empty(max(end, 2 * self._bytes.size), np.uint8)
            np.copyto(grown[:self._end], self._bytes[:self._end])
            self._bytes = grown
        np.copyto(self._bytes[self._at:end], data)
        self._at, self._end = end, max(self._end, end)
        return data.size

    def read(self, size: int = -1) -> bytes:     # what makes it a file
        end = self._end if size < 0 else min(self._end, self._at + size)
        data = self._bytes[self._at:end].tobytes()
        self._at = end
        return data

    def seek(self, offset: int, whence: int = 0) -> int:
        self._at = offset + (0, self._at, self._end)[whence]
        return self._at

    def tell(self) -> int:
        return self._at

    def flush(self) -> None:
        pass

    def getbuffer(self) -> memoryview:
        return memoryview(self._bytes[:self._end])


def _serialize_payload(payload: Dict[str, np.ndarray],
                       compress: str) -> Tuple[memoryview, int]:
    """npz bytes + file crc32 (the whole-file checksum the manifest
    records; verified before any byte of the delta is applied)."""
    from .utils import compress as compress_lib
    savez = np.savez_compressed \
        if compress_lib.check_persist_codec(compress) else np.savez
    out = _NpzBuffer(sum(np.asarray(v).nbytes + 4096
                         for v in payload.values()) + 4096)
    savez(out, **payload)
    raw = out.getbuffer()
    return raw, zlib.crc32(raw)


def _parse_payload(raw: bytes) -> Dict[str, np.ndarray]:
    # every caller checked the whole-file crc first, so a parse failure
    # here means crc-preserving corruption (or an unsupported npz
    # feature) — surface it typed, not as whatever np.load's zip/format
    # internals happen to raise (BadZipFile, struct.error, OSError...)
    try:
        data = np.load(io.BytesIO(raw))
        return {k: data[k] for k in data.files}
    except DeltaDecodeError:
        raise
    except Exception as e:  # noqa: BLE001 — parser surface, see above
        raise DeltaDecodeError(
            f"delta payload npz is unparseable ({len(raw)} bytes, "
            f"crc-verified): {type(e).__name__}: {e}") from e


def _verify_array_chunks(payload: Dict[str, np.ndarray],
                         chunk_crc: List[int]) -> bool:
    """Recompute per-chunk crcs of a parsed array payload.

    Never raises: ill-formed geometry (missing members, out-of-range
    chunk ids, non-list crcs — the manifest and the member bytes
    disagreeing) reports False, which the caller treats exactly like a
    chunk crc mismatch. Mirrored by the native reader's
    ``verify_chunk_crcs`` (oe_serving.cc) so both loaders classify the
    same manifests as damaged."""
    try:
        chunks = np.asarray(payload["chunks"], np.int64)
        R = int(payload["rows_per_chunk"])
        vocab = int(payload["vocab"])
        if R <= 0 or vocab < 0 or chunks.ndim != 1 \
                or len(chunk_crc) != chunks.size:
            return False
        if chunks.size and not (0 <= int(chunks.min())
                                and int(chunks.max()) < -(-vocab // R)):
            return False
        bounds = list(_chunk_bounds(chunks, R, vocab))
        return _crcs_match(payload, bounds,
                           bounds[-1][1] if bounds else 0, chunk_crc)
    except (KeyError, TypeError, ValueError, OverflowError):
        return False


def _crcs_match(payload, bounds, rows: int, want) -> bool:
    """Every field holds ``rows`` rows and the crcs of ``bounds`` are
    ``want``."""
    return all(payload[f].shape[0] == rows for f in _field_order(payload)) \
        and _row_crcs(payload, bounds) == [int(c) for c in want]


def _verify_array_blocks(payload: Dict[str, np.ndarray], block_crc,
                         block_rows) -> bool:
    """Recompute the per-block crcs of a format-2 array payload (blocks
    of ``block_rows`` payload rows, the last one short). Never raises;
    mirrored by the native reader's ``verify_block_crcs``."""
    try:
        block_rows = int(block_rows)
        rows = int(payload["weights"].shape[0])
        if block_rows <= 0 or isinstance(block_crc, (str, bytes)) \
                or len(block_crc) != -(-rows // block_rows):
            return False
        return _crcs_match(payload, _block_bounds(rows, block_rows), rows,
                           block_crc)
    except (KeyError, TypeError, ValueError, OverflowError,
            AttributeError):
        return False


# --- delta save --------------------------------------------------------------

@dataclasses.dataclass
class PendingDelta:
    """A delta save between its snapshot (:func:`begin_delta`) and its
    commit (:func:`finish_delta`): the claimed dirty sets, the staged
    rows, the chain entry it will become. At most one per directory at a
    time: the next :func:`begin_delta` reads the manifest this one
    commits."""
    path: str
    collection: Any
    manifest: Dict[str, Any]
    seq: int
    step: int
    snaps: Dict[str, np.ndarray]
    staged: Dict[str, Any]     # _StagedRows, _StagedKeys or a hash payload
    dense: Any
    options: Dict[str, Any]
    began: float
    snapshot_at: float


@functools.lru_cache(maxsize=None)
def _tree_copy_program():
    def ckpt_gather_dense(tree):
        return jax.tree.map(jnp.copy, tree)
    return jax.jit(ckpt_gather_dense)


def begin_delta(path: str, collection: EmbeddingCollection,
                states: Dict[str, Any], *, step: int,
                dense_state: Any = None,
                include_optimizer: bool = True,
                compress: str = "",
                model_sign: str = "",
                max_workers: Optional[int] = None,
                compact_chain_len: int = COMPACT_CHAIN_LEN,
                compact_bytes_ratio: float = COMPACT_BYTES_RATIO,
                background_compact: bool = True,
                return_payload: bool = False,
                extra: Optional[Dict[str, Any]] = None):
    """The snapshot half of a delta save (arguments: :func:`save_delta`).

    Claims every tracker's dirty set and stages what it names as of
    ``states``: a variable's rows by one gather program dispatched on the
    device's stream (:func:`_stage_array_rows`; :func:`_stage_hash_keys`
    for a hash variable tracked to the key), the dense pytree by a device
    copy, none waited for; only a hash variable tracked in ``key % n``
    chunks (``target_chunks``) is read to the host here, by a scan of the
    table. What this costs the calling thread reads under two spans:
    ``ckpt.claim`` (the trackers hand out their dirty sets) and
    ``ckpt.stage`` (a variable's ids to the device and its program's
    dispatch). Returns a :class:`PendingDelta` for
    :func:`finish_delta`. ``states`` may be donated once this returns.
    With no armed base in ``path`` nothing can be incremental: the full
    save runs here, blocking, and its info dict is returned instead.
    """
    from . import checkpoint as ckpt
    from .utils import compress as compress_lib
    from .utils import observability
    compress = compress_lib.check_persist_codec(compress)
    if fs.is_remote(path):
        raise ValueError(
            "mode='delta' needs a local path (the compactor folds chain "
            "files into the base in place); dump remote checkpoints full")
    if jax.process_count() > 1:
        raise ValueError("mode='delta' is single-process; multi-host "
                         "dumps use the full part format")
    trackers = collection.dirty_trackers
    if not trackers:
        raise ValueError(
            "mode='delta' needs dirty tracking: call "
            "collection.enable_dirty_tracking() before training")
    # a running background compaction owns the directory — join it (and
    # surface its error) before writing anything
    join_compactor(path)
    manifest = read_manifest(path)
    t0 = time.perf_counter()
    if manifest is None:
        # no armed base: the full save writes one and arms the chain
        nbytes = ckpt._save_checkpoint_impl(
            path, collection, states, dense_state=dense_state,
            include_optimizer=include_optimizer, model_sign=model_sign,
            compress="", step=step, max_workers=max_workers,
            extra=extra)
        dt = time.perf_counter() - t0
        observability.record_ckpt_save("full", nbytes, dt, chain_len=0)
        return {"mode": "full", "forced_full": True, "bytes": int(nbytes),
                "seconds": dt, "seq": 0}
    if bool(manifest.get("include_optimizer", True)) \
            != bool(include_optimizer):
        raise ValueError(
            "delta save include_optimizer does not match the base "
            f"(base={manifest.get('include_optimizer')}); re-save full")
    _gc_orphans(path, manifest["chain"])

    from .analysis import scope
    with scope.span("ckpt.claim"):
        snaps = {name: trackers[name].snapshot_clear() for name in trackers}
    staged: Dict[str, Any] = {}
    try:
        for name, chunks in snaps.items():
            if not chunks.size:
                continue
            state = hot_cache.unwrap(states[name])
            with scope.span("ckpt.stage"):
                if isinstance(trackers[name], KeyTracker):
                    staged[name] = _stage_hash_keys(
                        collection, name, state, chunks, include_optimizer)
                elif collection.specs[name].use_hash:
                    staged[name] = _hash_delta_payload(
                        state, trackers[name], chunks, include_optimizer)
                else:
                    staged[name] = _stage_array_rows(
                        collection, name, state, trackers[name], chunks,
                        include_optimizer)
        dense = None
        if dense_state is not None:
            dense = _tree_copy_program()(dense_state)
            for leaf in jax.tree.leaves(dense):
                leaf.copy_to_host_async()
    except BaseException:
        for name, chunks in snaps.items():
            trackers[name].restore(chunks)
        raise
    # the snapshot is taken: steps dispatched from here on run behind it,
    # and a kill from here to the commit leaves the previous chain
    sync_point("ckpt.delta.snapshot")
    return PendingDelta(
        path=path, collection=collection, manifest=manifest,
        seq=int(manifest["last_seq"]) + 1, step=int(step), snaps=snaps,
        staged=staged, dense=dense, began=t0,
        snapshot_at=time.perf_counter(),
        options=dict(compress=compress, max_workers=max_workers,
                     compact_chain_len=compact_chain_len,
                     compact_bytes_ratio=compact_bytes_ratio,
                     background_compact=background_compact,
                     return_payload=return_payload,
                     extra=dict(extra) if extra else None))


def finish_delta(pending: PendingDelta) -> Dict[str, Any]:
    """The commit half of a delta save, on any thread: the staged rows
    come to the host (span ``ckpt.d2h``: the wait for the steps ahead of
    the gather, the gather, the copy), are checksummed (``ckpt.checksum``)
    and written one file a variable (``ckpt.write``), and one manifest
    rename commits them (``ckpt.commit``). A failure anywhere restores
    every claimed dirty set to its tracker, so the next save carries
    those rows."""
    from . import checkpoint as ckpt
    from .analysis import scope
    from .utils import observability
    path, collection, manifest = \
        pending.path, pending.collection, pending.manifest
    opts, seq = pending.options, pending.seq
    trackers = collection.dirty_trackers
    results: Dict[str, Dict[str, Any]] = {}
    payloads: Dict[str, Dict[str, np.ndarray]] = {}

    def _write_var(name: str) -> None:
        sync_point("ckpt.delta.write")
        payload = payloads[name]
        info = {"kind": "array" if "rows_per_chunk" in payload else "hash",
                "rows": int(payload["weights"].shape[0])}
        if "chunks" in payload:
            info["dirty_chunks"] = int(payload["chunks"].size)
        else:
            info["keys_exact"] = True
        if info["kind"] == "array":
            with scope.span("ckpt.checksum"):
                R = int(payload["rows_per_chunk"])
                if R == 1:
                    info["block_rows"] = CRC_BLOCK_ROWS
                    info["block_crc"] = _row_crcs(payload, _block_bounds(
                        info["rows"], CRC_BLOCK_ROWS))
                else:
                    info["chunk_crc"] = _row_crcs(payload, _chunk_bounds(
                        payload["chunks"], R, int(payload["vocab"])))
        with scope.span("ckpt.write"):
            raw, crc = _serialize_payload(payload, opts["compress"])
            fname = _delta_fname(seq, collection.variable_id(name))
            with fs.open_atomic(fs.join(path, fname)) as f:
                f.write(raw)
        results[name] = {"file": fname, "bytes": len(raw),
                         "crc32": int(crc), **info}

    try:
        # DENSE params ride OUTSIDE the chain protocol: small, replicated,
        # rewritten whole (atomically) on every save — including a SKIPPED
        # one, so a dense-only training window still persists its params.
        # Last-writer-wins; a torn-tail recovery keeps the newest dense
        # file next to the recovered sparse state (document'd divergence —
        # chain guarantees cover the sparse tables).
        with scope.span("ckpt.d2h"):
            dense = jax.device_get(pending.dense)
            for name, rows in pending.staged.items():
                if isinstance(rows, _StagedRows):
                    payloads[name] = dict(rows.header, **{
                        f: np.asarray(a)[:rows.rows]
                        for f, a in zip(rows.fields, rows.arrays)})
                elif isinstance(rows, _StagedKeys):
                    payloads[name] = _keys_payload(rows)
                else:
                    payloads[name] = rows
        pending.staged = {}             # the staging buffers are free
        if dense is not None:
            from flax import serialization
            with fs.open_atomic(fs.join(path, ckpt.DENSE_FILE)) as f:
                f.write(serialization.to_bytes(dense))
        if not payloads:
            return {"mode": "delta", "seq": int(manifest["last_seq"]),
                    "skipped": True, "bytes": 0, "rows": 0,
                    "chain_len": len(manifest["chain"])}
        ckpt._run_writers([lambda n=name: _write_var(n)
                           for name in payloads],
                          max_workers=opts["max_workers"])
        entry = {"seq": seq, "step": pending.step,
                 "bytes": sum(i["bytes"] for i in results.values()),
                 "rows": sum(i["rows"] for i in results.values()),
                 "vars": {name: results[name] for name in payloads}}
        if opts["extra"]:
            entry["extra"] = opts["extra"]
        manifest["chain"].append(entry)
        manifest["last_seq"] = seq
        manifest["format"] = max(int(manifest["format"]), *(
            DELTA_FORMAT if "keys_exact" in i
            else _BLOCKED_FORMAT if "block_crc" in i else _CHUNKED_FORMAT
            for i in results.values()))
        # the commit point: before this rename readers replay the old
        # chain
        sync_point("ckpt.delta.commit")
        with scope.span("ckpt.commit"):
            _write_manifest(path, manifest)
    except BaseException:
        # failed write OR failed commit: restore every claim so the next
        # save re-covers it (completed-but-uncommitted files are
        # orphans, GC'd next save); marks that landed during the attempt
        # are preserved either way
        for name, chunks in pending.snaps.items():
            trackers[name].restore(chunks)
        raise
    now = time.perf_counter()
    dt = now - pending.began
    scope.HISTOGRAMS.observe("ckpt_commit_lag_s", now - pending.snapshot_at)
    observability.record_ckpt_save("delta", entry["bytes"], dt,
                                   chain_len=len(manifest["chain"]),
                                   rows=entry["rows"])
    info = {"mode": "delta", "seq": seq, "step": pending.step,
            "bytes": int(entry["bytes"]), "rows": int(entry["rows"]),
            "seconds": dt, "chain_len": len(manifest["chain"]),
            "skipped": False}
    if opts["return_payload"]:
        info["delta"] = Delta(seq=seq, step=pending.step, vars=payloads)
    if compact_due(manifest, _base_bytes(path),
                   chain_len=opts["compact_chain_len"],
                   bytes_ratio=opts["compact_bytes_ratio"]):
        compact(path, background=opts["background_compact"],
                max_workers=opts["max_workers"])
        info["compaction"] = "background" if opts["background_compact"] \
            else "done"
    return info


def save_delta(path: str, collection: EmbeddingCollection,
               states: Dict[str, Any], *, step: int, **options
               ) -> Dict[str, Any]:
    """One incremental save: what was marked dirty since the last save ->
    one new chain entry: :func:`begin_delta`, then :func:`finish_delta`
    on the caller's thread. Forces a FULL save when no armed base exists
    (first save into a directory, or the previous dump predates dirty
    tracking). See ``checkpoint.save_checkpoint`` for the public entry.

    ``dense_state``, ``include_optimizer``, ``compress``, ``model_sign``,
    ``max_workers``: as ``checkpoint.save_checkpoint``.
    ``compact_chain_len`` / ``compact_bytes_ratio`` /
    ``background_compact``: the chain budget (:func:`compact_due`) and
    whether the fold it triggers runs on a background thread.

    ``return_payload=True`` attaches the committed :class:`Delta` to the
    info dict (``info["delta"]``) straight from memory — the PUBLISH
    path for serving hot-swap. Prefer it over a post-save
    :func:`read_delta`: the background compactor may fold the chain
    (deleting the file) before a disk read lands.

    ``extra``: JSON-serializable caller bookkeeping committed WITH this
    entry (and carried into the manifest base when the save is forced
    full) — the elastic-resume channel: ``fit(autosave_every=)`` records
    ``{"fit": {step, epoch, cursor}}`` here and ``fit(resume_from=)``
    restores from the entry the load actually verifies, so a torn tail
    resumes one autosave earlier, never from a half-applied state.
    """
    pending = begin_delta(path, collection, states, step=step, **options)
    if isinstance(pending, dict):
        return pending
    return finish_delta(pending)


def _base_bytes(path: str) -> int:
    total = 0
    for d in os.listdir(path):
        if d.startswith("var_") and d.endswith(".d"):
            vd = os.path.join(path, d)
            for f in os.listdir(vd):
                if f.endswith(".npy"):
                    total += os.path.getsize(os.path.join(vd, f))
    return total


def compact_due(manifest: Dict[str, Any], base_bytes: int, *,
                chain_len: int = COMPACT_CHAIN_LEN,
                bytes_ratio: float = COMPACT_BYTES_RATIO) -> bool:
    """Chain budget: past ``chain_len`` entries, or chain bytes past
    ``bytes_ratio`` of the base — both bound replay time and file count
    over arbitrarily long runs (the reference's periodic rebase)."""
    chain = manifest.get("chain", [])
    if len(chain) >= chain_len:
        return True
    cb = sum(int(e.get("bytes", 0)) for e in chain)
    return base_bytes > 0 and cb >= bytes_ratio * base_bytes


# --- chain verification + replay ---------------------------------------------

def verify_chain(path: str, manifest: Dict[str, Any],
                 keep_payloads: bool = True
                 ) -> Tuple[List[Tuple[Dict[str, Any],
                                       Dict[str, Dict[str, np.ndarray]]]],
                            bool]:
    """Read + checksum every committed entry; returns ``(list of
    (entry, {var: payload}), dropped_last)``.

    A torn/corrupt/missing FINAL entry is DISCARDED whole (the state as
    of the previous entry is complete and consistent — a partial last
    delta must never be half-applied); the same damage mid-chain raises
    (later entries were built on top of it). ``keep_payloads=False``
    verifies without holding the parsed arrays (the compactor's
    bounded-memory pass; payloads are re-read one at a time during the
    fold — the chain-bytes budget can be a large fraction of the base,
    which must never be required to fit in RAM at once)."""
    entries = manifest.get("chain", [])
    if not isinstance(entries, list):
        raise DeltaDecodeError(
            f"delta chain at {path!r} is not a list (manifest corrupt)")
    out = []
    for i, entry in enumerate(entries):
        if (not isinstance(entry, dict) or "seq" not in entry
                or not isinstance(entry.get("vars"), dict)
                or not _seq_ok(entry.get("seq"))):
            # native parity (replay_delta_chain "corrupt delta chain
            # entry"): structural manifest corruption refuses the load
            # outright — tear semantics are reserved for FILE damage.
            # The seq bound matches the native json_i64 int64 range: a
            # 1e300 seq that Python's bignums would happily carry must
            # not load here while the native reader refuses it
            raise DeltaDecodeError(
                f"corrupt delta chain entry #{i} at {path!r}")
        payloads: Dict[str, Dict[str, np.ndarray]] = {}
        bad = None
        for name, info in entry["vars"].items():
            try:
                fname = info["file"]
                want_crc = int(info["crc32"])
                if not isinstance(fname, str):
                    raise TypeError(
                        f"file field is {type(fname).__name__}")
            except (TypeError, KeyError, ValueError) as e:
                bad = f"var {name!r}: malformed manifest record ({e})"
                break
            fpath = fs.join(path, fname)
            try:
                with fs.open_file(fpath, "rb") as f:
                    raw = f.read()
            except (OSError, FileNotFoundError):
                bad = f"{fname}: missing/unreadable"
                break
            if zlib.crc32(raw) != want_crc:
                bad = f"{fname}: crc mismatch"
                break
            payload = _parse_payload(raw)
            if info.get("chunk_crc") is not None \
                    and not _verify_array_chunks(payload,
                                                 info["chunk_crc"]):
                bad = f"{info['file']}: chunk checksum mismatch"
                break
            if info.get("block_crc") is not None \
                    and not _verify_array_blocks(payload,
                                                 info["block_crc"],
                                                 info.get("block_rows")):
                bad = f"{info['file']}: block checksum mismatch"
                break
            if keep_payloads:
                payloads[name] = payload
            del payload
        if bad is None:
            out.append((entry, payloads))
            continue
        if i == len(entries) - 1:
            warnings.warn(
                f"delta chain at {path!r}: final entry seq="
                f"{entry['seq']} is torn ({bad}); discarded — "
                "recovering to the last complete delta", RuntimeWarning)
            return out, True
        raise RuntimeError(
            f"delta chain at {path!r} is torn mid-chain at seq="
            f"{entry['seq']} ({bad}); later deltas build on it — "
            "restore the file or fall back to an older full checkpoint")
    return out, False


def _entry_payload(path: str, entry: Dict[str, Any],
                   name: str) -> Optional[Dict[str, np.ndarray]]:
    """Re-read one verified entry's payload for one variable (the
    compactor's one-at-a-time loader; crc already checked)."""
    info = entry["vars"].get(name)
    if info is None:
        return None
    with fs.open_file(fs.join(path, info["file"]), "rb") as f:
        return _parse_payload(f.read())


def replay_chain(path: str, collection: EmbeddingCollection,
                 states: Dict[str, Any], *, manifest: Dict[str, Any],
                 with_opt: bool, shard_slice: Optional[tuple],
                 dump_meta: Optional[Dict[str, Any]] = None,
                 info: Optional[Dict[str, Any]] = None
                 ) -> Dict[str, Any]:
    """Apply the committed chain over freshly-loaded base states, in
    order (newest wins by construction). Called by ``load_checkpoint``;
    states are UNWRAPPED table states (hot-cache wrap happens after).
    Payloads stream one ENTRY at a time (host memory bounded by one
    delta, never the whole chain — which the compaction budget allows
    to reach a large fraction of the base). ``info`` (when given) gets
    ``applied_seq`` AND ``resume_extra`` from the SAME verify pass the
    replay uses — the version (and the caller bookkeeping) the loaded
    states actually reflect: a dropped torn tail's extra is never
    surfaced."""
    verified, _dropped = verify_chain(path, manifest, keep_payloads=False)
    if info is not None:
        info["applied_seq"] = verified_seq(manifest, verified)
        info["resume_extra"] = resume_extra(manifest, verified)
    for entry, _ in verified:
        payloads = {name: _entry_payload(path, entry, name)
                    for name in entry["vars"]}
        states = apply_delta_to_states(
            collection, states, payloads, shard_slice=shard_slice,
            with_opt=with_opt, donate=True)
        del payloads
    return states


def verified_seq(manifest: Optional[Dict[str, Any]],
                 verified) -> int:
    """Version of an ALREADY-verified chain view: the last verified
    entry's seq, else the manifest's ``content_seq`` (what the base
    bytes reflect — after a compaction the chain is empty but the base
    carries every folded delta; pre-``content_seq`` manifests read 0,
    their pre-fix behavior). The loaders use THIS over the same verify
    pass their replay performs, so the version a model starts serving at
    can never race ahead of the rows it actually holds."""
    if manifest is None:
        return 0
    if verified:
        return int(verified[-1][0]["seq"])
    return int(manifest.get("content_seq", 0))


def resume_extra(manifest: Optional[Dict[str, Any]],
                 verified) -> Dict[str, Any]:
    """The ``extra`` bookkeeping of an ALREADY-verified chain view: the
    last verified entry's (the newest commit a load applies), else the
    manifest base's (what the base bytes were saved with). Same
    resolution discipline as :func:`verified_seq` — the extra a resume
    restores must describe exactly the rows the load delivered, so a
    dropped torn tail's extra (newer than the loaded content) is never
    returned, and an OLDER entry's is never substituted (its cursor
    would re-apply rows the newer content already holds)."""
    if manifest is None:
        return {}
    if verified:
        return dict(verified[-1][0].get("extra") or {})
    return dict(manifest.get("extra") or {})


def applied_seq(path: str) -> int:
    """Chain seq a load of ``path`` replays up to (torn tail excluded) —
    the hot-swap version a freshly loaded serving model starts at.

    Deliberately re-verifies the chain (one extra checksum pass per
    MODEL LOAD — rare and bounded): the version must reflect exactly
    what a load applies, including a dropped torn tail, and the
    manifest's ``last_seq`` alone cannot say that. NOTE: against a
    directory a trainer is actively saving into, prefer the version the
    load itself reports (``load_checkpoint(..., info=...)``) — this
    standalone read can see a NEWER chain than a just-finished load
    replayed, and a model versioned ahead of its rows acks the next
    delta as stale and loses it (graftproto found this divergence in
    the serving registry; fixed there)."""
    manifest = read_manifest(path)
    if manifest is None:
        return 0
    verified, _ = verify_chain(path, manifest, keep_payloads=False)
    return verified_seq(manifest, verified)


def apply_delta_to_states(collection: EmbeddingCollection,
                          states: Dict[str, Any],
                          payloads: Dict[str, Dict[str, np.ndarray]],
                          *, shard_slice: Optional[tuple] = None,
                          with_opt: bool = True,
                          donate: bool = True) -> Dict[str, Any]:
    """Patch variable states with delta payloads (functional: returns a
    NEW states dict; inputs stay valid unless ``donate``). Shared by the
    load-path replay (donate, with optimizer slots) and the serving
    hot-swap (no donation — in-flight readers keep the pre-swap state;
    serving's stateless optimizer carries no slots)."""
    out = dict(states)
    for name, payload in payloads.items():
        if name not in collection.specs:
            continue
        spec = collection.specs[name]
        state = hot_cache.unwrap(out[name])
        if "keys" in payload:
            if not spec.use_hash:
                raise ValueError(
                    f"delta for {name!r} is a hash payload but the "
                    "variable is bounded — delta chains cannot "
                    "category-swap; load the base full or re-save")
            state = _apply_hash_payload(collection, name, state, payload,
                                        shard_slice=shard_slice,
                                        with_opt=with_opt, donate=donate)
        else:
            if spec.use_hash:
                raise ValueError(
                    f"delta for {name!r} is an array payload but the "
                    "variable is hash — delta chains cannot "
                    "category-swap; load the base full or re-save")
            state = _apply_array_payload(collection, name, state, payload,
                                        shard_slice=shard_slice,
                                        with_opt=with_opt, donate=donate)
        out[name] = collection.wrap_hot_cache(name, state)
    return out


def _payload_ids(payload: Dict[str, np.ndarray]) -> np.ndarray:
    """Global logical row ids of an ARRAY payload's rows (chunk ranges
    expanded in order). Refuses ill-formed headers typed: a hostile
    chunk id or rows_per_chunk would otherwise expand to an unbounded
    ``arange`` (an allocation-of-death, not a parse error) — the native
    reader refuses the same ranges ("array delta chunk id out of
    range")."""
    try:
        chunks = np.asarray(payload["chunks"], np.int64)
        R = int(payload["rows_per_chunk"])
        vocab = int(payload["vocab"])
    except (KeyError, TypeError, ValueError, OverflowError) as e:
        raise DeltaDecodeError(
            f"corrupt array delta header: {type(e).__name__}: {e}"
        ) from e
    if R <= 0 or vocab < 0:
        raise DeltaDecodeError(
            f"corrupt array delta header (rows_per_chunk={R}, "
            f"vocab={vocab})")
    if not chunks.size:
        return np.zeros(0, np.int64)
    nchunks = -(-vocab // R)
    lo, hi = int(chunks.min()), int(chunks.max())
    if lo < 0 or hi >= nchunks:
        raise DeltaDecodeError(
            f"array delta chunk id out of range: [{lo}, {hi}] outside "
            f"[0, {nchunks})")
    return _chunk_rows(chunks, R, vocab)


def _apply_array_payload(collection, name, state, payload, *,
                         shard_slice, with_opt, donate):
    spec = collection.specs[name]
    sspec = collection.sharding_spec(name)
    dtype = np.dtype(table_lib.resolve_dtype(spec.meta()))
    ids = _payload_ids(payload)
    fields = [("weights", dtype)]
    if with_opt:
        for sname, sval in state.slots.items():
            if f"slot_{sname}" in payload:
                fields.append((f"slot_{sname}",
                               np.dtype(sval.dtype)))
    weights = state.weights
    slots = dict(state.slots)
    size = min(_APPLY_CHUNK, max(int(ids.size), 1))
    for lo in range(0, ids.size, size):
        sub = ids[lo:lo + size]
        if shard_slice is not None:
            # serving shard group: keep owned global ids, map to the
            # local row space (local l holds id l*G + k)
            k, G = shard_slice
            sel = (sub % G) == k
            local_ids = sub[sel] // G
        else:
            sel = None
            local_ids = sub
        shard, local = sspec.shard_and_local(local_ids)
        phys = shard * sspec.rows_per_shard + local
        n = phys.shape[0]
        phys_p = np.full((size,), -1, np.int64)
        phys_p[:n] = phys
        jphys = jnp.asarray(phys_p)
        for fname, fdtype in fields:
            rows = payload[fname][lo:lo + size]
            if sel is not None:
                rows = rows[sel]
            buf = np.zeros((size,) + rows.shape[1:], fdtype)
            buf[:n] = fs.view_as(np.asarray(rows), fdtype)
            target = weights if fname == "weights" \
                else slots[fname[len("slot_"):]]
            patched = st.deliver_rows_sharded(
                target, jphys, jnp.asarray(buf), mesh=collection.mesh,
                spec=sspec, donate=donate)
            if fname == "weights":
                weights = patched
            else:
                slots[fname[len("slot_"):]] = patched
    return table_lib.TableState(weights=weights, slots=slots)


def _apply_hash_payload(collection, name, state, payload, *,
                        shard_slice, with_opt, donate=True):
    sspec = collection.sharding_spec(name)
    keys = np.asarray(payload["keys"])
    key_dtype = np.dtype(state.keys.dtype)
    empty = hash_lib.empty_key(key_dtype)
    table_wide = hash_lib.is_wide(state.keys)
    payload_wide = keys.ndim == 2
    if table_wide != payload_wide:
        raise ValueError(
            f"delta for {name!r}: key width mismatch (payload "
            f"{'wide' if payload_wide else 'narrow'}, table "
            f"{'wide' if table_wide else 'narrow'}) — delta chains "
            "cannot key-migrate; load the base full instead")
    slot_names = [s for s in state.slots
                  if with_opt and f"slot_{s}" in payload] if with_opt \
        else []
    wdtype = np.dtype(state.weights.dtype)
    before = state.insert_failures
    n = keys.shape[0]
    size = min(_APPLY_CHUNK, max(n, 1))
    for lo in range(0, n, size):
        sub = keys[lo:lo + size]
        got = sub.shape[0]
        ck = np.full((size,) + sub.shape[1:], empty, dtype=key_dtype)
        ck[:got] = sub.astype(key_dtype)
        if shard_slice is not None:
            k, G = shard_slice
            ids64 = hash_lib.join64(sub) if payload_wide \
                else sub.astype(np.int64)
            ck[:got][(ids64 % G) != k] = empty
        cw = np.zeros((size,) + payload["weights"].shape[1:], wdtype)
        cw[:got] = fs.view_as(
            np.asarray(payload["weights"][lo:lo + size]), wdtype)
        srows = {}
        for sname in slot_names:
            sdtype = np.dtype(state.slots[sname].dtype)
            block = payload[f"slot_{sname}"][lo:lo + size]
            cs = np.zeros((size,) + block.shape[1:], sdtype)
            cs[:got] = fs.view_as(np.asarray(block), sdtype)
            srows[sname] = jnp.asarray(cs)
        state = sh.insert_rows_sharded(
            state, jnp.asarray(ck), jnp.asarray(cw), srows,
            mesh=collection.mesh, spec=sspec, donate=donate)
    grew = int(jax.device_get(state.insert_failures - before))
    if grew > 0:
        raise RuntimeError(
            f"hash variable {name!r}: {grew} delta rows did not fit "
            "(hash_capacity too small); a delta apply must deliver "
            "every row or fail")
    return state


# --- hot-swap payloads (the train->serve stream) -----------------------------

@dataclasses.dataclass
class Delta:
    """One committed delta as an in-memory payload — the unit the
    trainer publishes and ``ModelRegistry.apply_delta`` patches in.
    ``vars`` holds the same per-variable dicts the chain files store."""

    seq: int
    step: int
    vars: Dict[str, Dict[str, np.ndarray]]

    @property
    def rows(self) -> int:
        return sum(int(p["weights"].shape[0]) for p in self.vars.values())


def read_delta(path: str, seq: Optional[int] = None) -> Delta:
    """Load one committed delta (default: the newest) for publishing."""
    manifest = read_manifest(path)
    if manifest is None or not manifest.get("chain"):
        raise ValueError(f"no committed deltas at {path!r}")
    entries = manifest["chain"]
    if not isinstance(entries, list):
        raise DeltaDecodeError(
            f"delta chain at {path!r} is not a list (manifest corrupt)")
    if seq is None:
        entry = entries[-1]
    else:
        match = [e for e in entries
                 if isinstance(e, dict) and e.get("seq") == seq]
        if not match:
            raise KeyError(
                f"no delta seq={seq} at {path!r} (chain has "
                f"{[e.get('seq') for e in entries if isinstance(e, dict)]})")
        entry = match[0]
    try:
        eseq = int(entry["seq"])
        estep = int(entry["step"])
        var_items = list(entry["vars"].items())
    except (TypeError, ValueError, KeyError, AttributeError) as e:
        raise DeltaDecodeError(
            f"corrupt delta chain entry at {path!r}: "
            f"{type(e).__name__}: {e}") from e
    payloads = {}
    for name, info in var_items:
        try:
            fname = info["file"]
            want_crc = int(info["crc32"])
            if not isinstance(fname, str):
                raise TypeError(f"file field is {type(fname).__name__}")
        except (TypeError, KeyError, ValueError) as e:
            raise DeltaDecodeError(
                f"corrupt delta manifest record for {name!r} at "
                f"{path!r}: {type(e).__name__}: {e}") from e
        with fs.open_file(fs.join(path, fname), "rb") as f:
            raw = f.read()
        if zlib.crc32(raw) != want_crc:
            raise RuntimeError(
                f"delta seq={eseq} file {fname} fails "
                "its checksum; refusing to publish a corrupt delta")
        payloads[name] = _parse_payload(raw)
    return Delta(seq=eseq, step=estep, vars=payloads)


def read_deltas_since(path: str, after_seq: int) -> List[Delta]:
    """Committed deltas with ``seq > after_seq``, in order — the catch-up
    stream for a replica that fell behind."""
    manifest = read_manifest(path)
    if manifest is None:
        return []
    chain = manifest.get("chain") or []
    try:
        seqs = [int(e["seq"]) for e in chain]
        if not all(_seq_ok(s) for s in seqs):
            raise ValueError("seq outside the int64 range")
    except (TypeError, ValueError, KeyError) as e:
        raise DeltaDecodeError(
            f"corrupt delta chain at {path!r}: "
            f"{type(e).__name__}: {e}") from e
    return [read_delta(path, s) for s in seqs if s > int(after_seq)]


def encode_delta(delta: Delta, compress: str = "") -> bytes:
    """Wire-frame a delta: one JSON header line (seq/step/field specs)
    + concatenated raw array bytes, optionally compressed — the same
    header-line + packed-body shape as the serving ``lookup_bin`` and
    peer-restore row pages."""
    from .utils import compress as compress_lib
    compress = compress_lib.check(compress)
    head: Dict[str, Any] = {"seq": delta.seq, "step": delta.step,
                            "vars": {}}
    body = bytearray()
    for name in sorted(delta.vars):
        payload = delta.vars[name]
        specs = []
        for f in sorted(payload):
            arr = np.ascontiguousarray(np.asarray(payload[f]))
            specs.append([f, np.lib.format.dtype_to_descr(arr.dtype),
                          list(arr.shape)])
            body += arr.tobytes()
        head["vars"][name] = specs
    raw = bytes(body)
    if compress:
        head["compress"] = compress
        raw = compress_lib.compress(compress, raw)
    return json.dumps(head).encode() + b"\n" + raw


def decode_delta(data: bytes) -> Delta:
    """Decode one :func:`encode_delta` wire frame.

    The frame is UNTRUSTED bytes (the REST ``POST /models/<sign>/delta``
    body): every malformed shape — missing header line, garbage JSON,
    bad codec, corrupt field specs, a body too short for its specs —
    refuses with :class:`DeltaDecodeError` carrying offset context, so
    the REST handler answers 400 and the graftfuzz oracle sees a typed
    refusal instead of a raw ``struct.error``/``zlib.error``/
    ``KeyError`` escaping the parser."""
    from .utils import compress as compress_lib
    data = bytes(data)
    nl = data.find(b"\n")
    if nl < 0:
        raise DeltaDecodeError(
            f"delta wire frame has no header line ({len(data)} bytes, "
            "no newline)")
    try:
        head = _loads_bounded(data[:nl].decode("utf-8"),
                              "delta wire header")
    except DeltaDecodeError:
        raise
    except ValueError as e:
        raise DeltaDecodeError(
            f"delta wire header (bytes 0..{nl}) is not valid JSON: {e}"
        ) from e
    if not isinstance(head, dict):
        raise DeltaDecodeError(
            f"delta wire header is JSON {type(head).__name__}, "
            "not an object")
    raw = data[nl + 1:]
    codec = head.get("compress", "")
    if codec:
        try:
            raw = compress_lib.decompress(codec, raw)
        except DeltaDecodeError:
            raise
        except Exception as e:  # noqa: BLE001 — zlib.error/bad codec
            raise DeltaDecodeError(
                f"delta wire body (offset {nl + 1}) fails {codec!r} "
                f"decompression: {type(e).__name__}: {e}") from e
    try:
        seq = int(head["seq"])
        step = int(head["step"])
        var_specs = head["vars"]
    except (KeyError, TypeError, ValueError) as e:
        raise DeltaDecodeError(
            f"delta wire header missing/corrupt field: "
            f"{type(e).__name__}: {e}") from e
    if not isinstance(var_specs, dict):
        raise DeltaDecodeError(
            f"delta wire header 'vars' is JSON "
            f"{type(var_specs).__name__}, not an object")
    off = 0
    out: Dict[str, Dict[str, np.ndarray]] = {}
    for name, specs in var_specs.items():
        if not isinstance(specs, list):
            raise DeltaDecodeError(
                f"delta wire specs for {name!r} are not a list")
        payload = {}
        for spec in specs:
            try:
                f, descr, shape = spec
                dtype = np.dtype(np.lib.format.descr_to_dtype(descr))
                dims = [int(d) for d in shape]
            except (TypeError, ValueError, KeyError) as e:
                raise DeltaDecodeError(
                    f"corrupt field spec {spec!r} for {name!r}: "
                    f"{type(e).__name__}: {e}") from e
            if any(d < 0 for d in dims):
                raise DeltaDecodeError(
                    f"negative dim in field spec {spec!r} for {name!r}")
            count = 1
            for d in dims:
                count *= d
            nb = (count if dims else 1) * dtype.itemsize
            if off + nb > len(raw):
                raise DeltaDecodeError(
                    f"delta wire body truncated: field {f!r} of "
                    f"{name!r} needs body bytes [{off}, {off + nb}) "
                    f"but the body holds {len(raw)}")
            try:
                arr = np.frombuffer(raw[off:off + nb], dtype=dtype)
                payload[f] = arr.reshape(dims) if dims else arr[0]
            except (ValueError, IndexError) as e:
                raise DeltaDecodeError(
                    f"field {f!r} of {name!r} does not decode as "
                    f"{descr!r} x {dims}: {type(e).__name__}: {e}"
                ) from e
            off += nb
        out[name] = payload
    return Delta(seq=seq, step=step, vars=out)


# --- the compactor -----------------------------------------------------------

class _Compactor:
    def __init__(self, thread: threading.Thread):
        self.thread = thread
        self.err: Optional[BaseException] = None


_COMPACT_LOCK = make_lock("ckpt.compactors")
_COMPACTORS: Dict[str, _Compactor] = {}


def join_compactor(path: str) -> None:
    """Join (and surface the error of) any background compaction of
    ``path``. Every delta save calls this first — the compactor and the
    saver are the directory's only writers and never run concurrently."""
    key = os.path.realpath(path)
    with _COMPACT_LOCK:
        holder = _COMPACTORS.pop(key, None)
    if holder is None:
        return
    holder.thread.join()
    if holder.err is not None:
        raise RuntimeError("background chain compaction failed") \
            from holder.err


def compact(path: str, *, background: bool = False,
            max_workers: Optional[int] = None) -> Optional[Dict[str, Any]]:
    """Fold the committed chain into a new base ON DISK.

    Pure file work (base memmaps + chain payloads; no device, no live
    states), so it runs on a background thread while training continues.
    CRASH-SAFE by idempotence: folding performs exactly the newest-wins
    assignments the load-time replay would, and each base file commits
    via tmp + atomic rename — a kill mid-compaction leaves the OLD
    manifest (still referencing the chain) over partially-folded base
    files, and replaying the chain over a partially-folded base yields
    the identical state. The new manifest (empty chain, new base_id,
    ``last_seq`` preserved — seqs are burned, never reused) is the
    single commit point; superseded delta files are GC'd after it.
    """
    if background:
        key = os.path.realpath(path)
        join_compactor(path)
        holder_ref: List[_Compactor] = []

        def _run():
            sync_point("ckpt.compact.run")
            try:
                _compact_impl(path, max_workers=max_workers)
            except BaseException as e:  # noqa: BLE001 — re-raised at join
                holder_ref[0].err = e

        t = threading.Thread(target=_run, daemon=False,
                             name="oe-ckpt-compact")
        holder = _Compactor(t)
        holder_ref.append(holder)
        with _COMPACT_LOCK:
            _COMPACTORS[key] = holder
        t.start()
        return None
    return _compact_impl(path, max_workers=max_workers)


def _compact_impl(path: str, *,
                  max_workers: Optional[int] = None) -> Dict[str, Any]:
    from . import checkpoint as ckpt
    from .meta import ModelMeta, UNBOUNDED_VOCAB
    manifest = read_manifest(path)
    if manifest is None or not manifest["chain"]:
        return {"compacted": False}
    # bounded-memory verification: payloads re-read one at a time below.
    # A MID-chain tear raises out of verify_chain: refuse to compact
    # (graceful — compaction is an optimization; the damage keeps
    # surfacing loudly at every load until a full save), never fail the
    # delta save that happened to trigger the fold
    try:
        verified, dropped = verify_chain(path, manifest,
                                         keep_payloads=False)
    except RuntimeError as e:
        warnings.warn(
            f"delta chain at {path!r}: refusing to compact a chain that "
            f"does not verify ({e}); re-save full to restore durability",
            RuntimeWarning)
        return {"compacted": False, "error": str(e)}
    entries = [e for e, _p in verified]
    if dropped:
        # graftproto true positive: a torn COMMITTED entry must not be
        # compacted away. Folding the verified prefix and GC'ing the
        # torn file would let later deltas commit over the hole with
        # the torn delta's chunks permanently lost (they were claim-
        # cleared at its save; nothing re-covers them) — and loads
        # would "succeed" on the folded base instead of hitting the
        # documented loud mid-chain refusal. Abort untouched: loads
        # keep their drop-the-tail recovery, and once a later delta
        # lands the tear is mid-chain and every load fails loudly until
        # a full save rebuilds the base from the live state.
        torn = manifest["chain"][len(entries)]["seq"]
        warnings.warn(
            f"delta chain at {path!r}: refusing to compact across torn "
            f"entry seq={torn}; re-save full to restore durability",
            RuntimeWarning)
        return {"compacted": False, "torn_seq": int(torn)}
    with fs.open_file(fs.join(path, ckpt.MODEL_META_FILE), "rb") as f:
        meta = ModelMeta.loads(f.read().decode("utf-8"))
    by_name = {v.name: v for v in meta.variables}
    # fold per variable: every chain payload for it, in order
    folded_steps = [e["step"] for e in entries]
    for name, v in by_name.items():
        has = [e for e in entries if name in e["vars"]]
        if not has:
            continue
        vdir = os.path.join(path, ckpt._var_dir(v.variable_id, name))
        if v.meta.vocabulary_size >= UNBOUNDED_VOCAB:
            # hash folds need every payload's keys up front for the
            # newest-wins merge + sizing; hash deltas carry live rows
            # only, so this is the dirty working set, not the table
            _fold_hash_var(vdir, [_entry_payload(path, e, name)
                                  for e in has])
        else:
            _fold_array_var(vdir, path, has, name,
                            max_workers=max_workers)
    new_manifest = {"format": _CHUNKED_FORMAT,
                    "base_id": uuid.uuid4().hex,
                    "base_step": int(folded_steps[-1]) if folded_steps
                    else manifest["base_step"],
                    "include_optimizer":
                        bool(manifest.get("include_optimizer", True)),
                    "last_seq": int(manifest["last_seq"]),
                    # the folded base now REFLECTS the whole verified
                    # chain: record it so applied_seq of the chainless
                    # manifest reports the true version, not 0 (which
                    # wedged hot-swap behind gap refusals after every
                    # compaction — graftproto compact_zero_version)
                    "content_seq": int(entries[-1]["seq"]) if entries
                    else int(manifest.get("content_seq", 0)),
                    # the folded base absorbs the NEWEST folded entry's
                    # resume extra (the model's comp_commit carrying
                    # base_cursor forward) — dropping it would silently
                    # rewind every elastic resume to cursor 0 after the
                    # first compaction. Newest entry ONLY: an older
                    # entry's cursor under newer content re-applies rows
                    "extra": dict(entries[-1].get("extra") or {}),
                    "chain": []}
    sync_point("ckpt.compact.commit")
    _write_manifest(path, new_manifest)
    _gc_orphans(path, chain=())
    return {"compacted": True, "folded": len(verified),
            "last_seq": new_manifest["last_seq"]}


def _commit_file(tmp: str, final: str) -> None:
    os.replace(tmp, final)


def _fold_array_var(vdir: str, path: str, entries: List[Dict[str, Any]],
                    name: str,
                    max_workers: Optional[int] = None) -> None:
    """New base field files = old base with every payload's chunk rows
    overwritten (in chain order; later payloads win by overwrite).
    Payloads are loaded ONE AT A TIME (memory stays bounded by one
    delta, not the chain)."""
    from . import checkpoint as ckpt
    fields = sorted(f[:-4] for f in os.listdir(vdir)
                    if f.endswith(".npy"))
    srcs, dsts = {}, {}
    tasks = []
    for field in fields:
        src_path = os.path.join(vdir, field + ".npy")
        src = np.load(src_path, mmap_mode="r")
        dst = np.lib.format.open_memmap(
            src_path + ".compact.tmp", mode="w+",
            dtype=src.dtype, shape=src.shape)
        srcs[field], dsts[field] = src, dst
        row_bytes = max(1, src.nbytes // max(1, src.shape[0]))
        win = max(1, ckpt._PAR_WINDOW_BYTES // row_bytes)
        for lo in range(0, src.shape[0], win):
            hi = min(src.shape[0], lo + win)
            tasks.append(lambda lo=lo, hi=hi, src=src, dst=dst:
                         dst.__setitem__(slice(lo, hi), src[lo:hi]))
    ckpt._run_writers(tasks, max_workers=max_workers)
    for entry in entries:
        payload = _entry_payload(path, entry, name)
        if payload is None:
            continue
        ids = _payload_ids(payload)
        for field in fields:
            if field not in payload:
                continue
            # delta-sized scatter (random IO bounded by the delta, not
            # the base)
            dsts[field][ids] = fs.view_as(np.asarray(payload[field]),
                                          srcs[field].dtype)
        del payload
    for field in fields:
        dsts[field].flush()
        del dsts[field], srcs[field]
        _commit_file(os.path.join(vdir, field + ".npy.compact.tmp"),
                     os.path.join(vdir, field + ".npy"))


def _fold_hash_var(vdir: str, payloads: List[Dict[str, np.ndarray]]
                   ) -> None:
    """New base = old live rows with payload rows merged newest-wins by
    64-bit key; keys absent from the base append at the end."""
    key_path = os.path.join(vdir, "keys.npy")
    base_keys = np.load(key_path, mmap_mode="r")
    wide = base_keys.ndim == 2
    k64_base = hash_lib.join64(np.asarray(base_keys)) if wide \
        else np.asarray(base_keys).astype(np.int64)
    order = np.argsort(k64_base, kind="stable")
    sorted_base = k64_base[order]
    # newest-wins merge across payloads: last occurrence of each key
    all_k, all_src = [], []
    for pi, payload in enumerate(payloads):
        pk = np.asarray(payload["keys"])
        k64 = hash_lib.join64(pk) if pk.ndim == 2 \
            else pk.astype(np.int64)
        all_k.append(k64)
        all_src.append(np.stack(
            [np.full(k64.shape, pi, np.int64),
             np.arange(k64.shape[0], dtype=np.int64)], axis=1))
    cat_k = np.concatenate(all_k) if all_k else np.zeros(0, np.int64)
    cat_src = np.concatenate(all_src) if all_src \
        else np.zeros((0, 2), np.int64)
    rev_k = cat_k[::-1]
    uniq, ridx = np.unique(rev_k, return_index=True)
    take = cat_k.shape[0] - 1 - ridx          # last occurrence, keys sorted
    src = cat_src[take]
    pos = np.searchsorted(sorted_base, uniq)
    pos_c = np.minimum(pos, max(0, sorted_base.shape[0] - 1))
    hit = (pos < sorted_base.shape[0]) & (sorted_base[pos_c] == uniq) \
        if sorted_base.size else np.zeros(uniq.shape, bool)
    exist_rows = order[pos_c[hit]] if sorted_base.size \
        else np.zeros(0, np.int64)
    new_src = src[~hit]
    n_base = int(base_keys.shape[0])
    total = n_base + int(new_src.shape[0])
    fields = sorted(f[:-4] for f in os.listdir(vdir)
                    if f.endswith(".npy"))
    del base_keys
    for field in fields:
        src_path = os.path.join(vdir, field + ".npy")
        base = np.load(src_path, mmap_mode="r")
        tmp_path = src_path + ".compact.tmp"
        dst = np.lib.format.open_memmap(
            tmp_path, mode="w+", dtype=base.dtype,
            shape=(total,) + base.shape[1:])
        chunk = max(1, (32 << 20) // max(1, base.nbytes
                                         // max(1, n_base or 1)))
        for lo in range(0, n_base, chunk):
            hi = min(n_base, lo + chunk)
            dst[lo:hi] = base[lo:hi]

        def rows_for(sel_src):
            parts = []
            for pi, payload in enumerate(payloads):
                mask = sel_src[:, 0] == pi
                if mask.any():
                    parts.append((mask, payload[field][sel_src[mask, 1]]))
            out = None
            for mask, rows in parts:
                if out is None:
                    out = np.zeros((sel_src.shape[0],) + rows.shape[1:],
                                   base.dtype)
                out[mask] = fs.view_as(np.asarray(rows), base.dtype)
            return out

        if exist_rows.size:
            upd = rows_for(src[hit])
            if upd is not None:
                dst[exist_rows] = upd
        if new_src.size:
            app = rows_for(new_src)
            if app is not None:
                dst[n_base:] = app
        dst.flush()
        del dst, base
        _commit_file(tmp_path, src_path)

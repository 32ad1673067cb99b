"""graftfuzz — differential fuzzing over the untrusted-bytes surface.

Fifth static-gate leg (after graftlint/graftrace/graftcheck/graftproto):
the four existing legs reason about the package's OWN code, none of them
sees the parsers that consume bytes the package did not write — the
native checkpoint reader (``native/oe_serving.cc``: npz central
directory, delta-chain replay, crc32, zip64/deflate refusal), the Python
delta readers (``checkpoint_delta.py`` ``load_checkpoint`` replay /
``read_deltas_since`` / ``decode_delta``) and the ingest framers
(TFRecord length+crc framing, Criteo TSV rows). PR 12 found real
memory-safety bugs here by hand (crafted ``name_len`` SIGSEGV, uint32
local-header-offset overflow); this module makes that search mechanical,
deterministic and gated.

Three lanes, one seeded PRNG (every run replayable from ``--seed``):

* **ckpt** — structure-aware mutations of a real delta-chain checkpoint
  directory: bit flips (crc-caught and crc-PRESERVING — the latter
  proves the checksum is actually checked, not just present), tail and
  mid-chain truncations, npz central-directory/local-header field
  mutations (name_len, offset overflow, zip64 markers, stored->deflate
  method swaps, EOCD damage, .npy descr swaps), manifest field
  mutations (crc swap, seq gap/dupe/overflow, base_id swap, chunk-crc
  corruption, payload swaps with and without matching crcs, structural
  JSON garbage, 2000-deep nesting), and model_meta field fuzz.
* **wire** — ``encode_delta`` frames (the REST ``POST /models/<sign>/
  delta`` body): truncation, bit flips, header-JSON structure fuzz
  (huge/negative shapes, bad descrs, bogus codecs), magic garbage.
* **ingest** — synthetic Criteo shards (``write_synthetic_shards``)
  with TFRecord length/crc32c corruption, mid-record truncation and
  raw-bytes TSV splices, consumed through :class:`ShardStream`.

**Oracle — differential trichotomy.** For every mutated checkpoint
directory each reader (Python full loader, Python delta reader, native
reader under BOTH ASan and UBSan builds, each native probe in its own
subprocess so a sanitizer abort kills the probe, never the harness)
must either (a) load and bit-agree with every other loaded reader on
``(version, row-digest)``, (b) refuse with a clean TYPED error
(``DeltaDecodeError``/``ValueError``/``KeyError``/``RuntimeError``/
``OSError`` for Python; ``oe_model_load -> NULL`` + ``oe_last_error``
for native), or (c) recover to the same documented version (the
torn-final contract — recovery IS a load, at a lower version, so (c)
reduces to (a)). Never a SIGSEGV, never UB, never a hang past the
deadline, never an untyped Python exception escaping a byte parser,
never a silent Python-vs-native divergence. The wire lane additionally
decodes every frame twice and demands bit-identical results; the ingest
lane demands skip-and-count (``ingest_bad_rows``) or a loud typed
failure within the deadline — a dead reader must never hang the ring.

Coverage is accounted per mutation class and the CLI
(``python -m tools.graftfuzz``) exits nonzero on any violation OR any
declared class that never fired — the same no-hollow-exploration
discipline graftproto v2 pins with state-count floors. Reports carry no
wall-clock: two runs with the same seed are byte-identical.

This file doubles as the native-probe SUBPROCESS (``python fuzz.py
--native-probe`` with a JSON spec on stdin): module-level imports stay
stdlib-only so the probe starts in milliseconds without jax/numpy.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import shutil
import struct
import subprocess
import sys
import threading
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

DEADLINE_S = 30.0
MANIFEST = "delta_manifest"

# Typed-refusal set for Python probes: DeltaDecodeError subclasses
# ValueError; RecursionError subclasses RuntimeError; FileNotFoundError
# subclasses OSError. struct.error / zlib.error / BadZipFile /
# AttributeError / TypeError escaping a parser are scored as crashes.
PY_REFUSALS = (ValueError, KeyError, RuntimeError, OSError)


# --- report hygiene ----------------------------------------------------------

def _scrub(text: str, roots: List[str]) -> str:
    """Strip run-local tmp paths so reports are byte-stable across runs."""
    for r in roots:
        if r:
            text = text.replace(r, "<tmp>")
    return text


# --- zip byte surgery (stdlib struct; mirrors what oe_serving parses) --------

def _u16(buf: bytes, off: int) -> int:
    return struct.unpack_from("<H", buf, off)[0]


def _u32(buf: bytes, off: int) -> int:
    return struct.unpack_from("<I", buf, off)[0]


def _p16(buf: bytearray, off: int, v: int) -> None:
    struct.pack_into("<H", buf, off, v & 0xFFFF)


def _p32(buf: bytearray, off: int, v: int) -> None:
    struct.pack_into("<I", buf, off, v & 0xFFFFFFFF)


def _eocd_offset(buf: bytes) -> int:
    lo = max(0, len(buf) - 65557)
    off = bytes(buf).rfind(b"PK\x05\x06", lo)
    if off < 0:
        raise ValueError("no EOCD in npz")
    return off


def _central_entries(buf: bytes) -> Tuple[List[Dict[str, int]], int]:
    """Central-directory entries of an npz (field OFFSETS for patching)."""
    eocd = _eocd_offset(buf)
    n = _u16(buf, eocd + 10)
    off = _u32(buf, eocd + 16)
    out: List[Dict[str, int]] = []
    for _ in range(n):
        if buf[off:off + 4] != b"PK\x01\x02":
            break
        nlen = _u16(buf, off + 28)
        xlen = _u16(buf, off + 30)
        clen = _u16(buf, off + 32)
        out.append({
            "off": off,
            "method_off": off + 10,
            "crc_off": off + 16,
            "csize_off": off + 20,
            "usize_off": off + 24,
            "nlen_off": off + 28,
            "lho_off": off + 42,
            "name": bytes(buf[off + 46:off + 46 + nlen]).decode(
                "latin-1"),
            "lho": _u32(buf, off + 42),
        })
        off += 46 + nlen + xlen + clen
    if not out:
        raise ValueError("no central entries in npz")
    return out, eocd


# --- manifest surgery --------------------------------------------------------

def _load_m(d: str) -> Dict[str, Any]:
    with open(os.path.join(d, MANIFEST)) as f:
        return json.load(f)


def _store_m(d: str, m: Any) -> None:
    with open(os.path.join(d, MANIFEST), "w") as f:
        json.dump(m, f)


def _chain_recs(m: Dict[str, Any]) -> List[Tuple[int, str, Dict[str, Any]]]:
    out = []
    for ei, entry in enumerate(m.get("chain", [])):
        for name in sorted(entry["vars"]):
            out.append((ei, name, entry["vars"][name]))
    return out


def _refresh_crc(d: str, m: Dict[str, Any], fname: str) -> None:
    """Recompute a chain file's whole-file crc32 in the manifest — used
    by STRUCTURAL mutators so their damage reaches the parser instead of
    being masked by the (already-tested) file checksum."""
    with open(os.path.join(d, fname), "rb") as f:
        crc = zlib.crc32(f.read())
    for _, _, rec in _chain_recs(m):
        if rec.get("file") == fname:
            rec["crc32"] = int(crc)


def _pick_rec(rng: random.Random, d: str, m: Dict[str, Any],
              entry: Optional[int] = None,
              kind: Optional[str] = None) -> Tuple[int, str, Dict[str, Any]]:
    recs = [(ei, name, rec) for ei, name, rec in _chain_recs(m)
            if (entry is None or ei == entry)
            and (kind is None or rec.get("kind") == kind)]
    if not recs:
        raise ValueError(f"no chain records (entry={entry}, kind={kind})")
    return recs[rng.randrange(len(recs))]


def _mutate_file_bytes(d: str, fname: str,
                       fn: Callable[[bytearray], str]) -> str:
    p = os.path.join(d, fname)
    with open(p, "rb") as f:
        buf = bytearray(f.read())
    note = fn(buf)
    with open(p, "wb") as f:
        f.write(buf)
    return note


# --- ckpt-lane mutation classes ----------------------------------------------
# Every mutator: fn(rng, dirpath) -> note string (no absolute paths).

def _m_npz_bitflip(rng: random.Random, d: str) -> str:
    """Random bit flips in a chain file; the manifest crc is NOT fixed,
    so the whole-file checksum must catch it (tear semantics)."""
    m = _load_m(d)
    _, _, rec = _pick_rec(rng, d, m)

    def flip(buf: bytearray) -> str:
        n = rng.randint(1, 8)
        for _ in range(n):
            i = rng.randrange(len(buf))
            buf[i] ^= 1 << rng.randrange(8)
        return f"{rec['file']}: {n} bit flips, crc stale"
    return _mutate_file_bytes(d, rec["file"], flip)


def _m_npz_bitflip_crc_fixed(rng: random.Random, d: str) -> str:
    """Bit flips WITH the manifest whole-file crc re-stamped: reaches
    the npz parser / chunk-crc layer — proves the inner defenses hold
    when the outer checksum has been laundered."""
    m = _load_m(d)
    _, _, rec = _pick_rec(rng, d, m)

    def flip(buf: bytearray) -> str:
        n = rng.randint(1, 8)
        for _ in range(n):
            i = rng.randrange(len(buf))
            buf[i] ^= 1 << rng.randrange(8)
        return f"{rec['file']}: {n} bit flips, crc re-stamped"
    note = _mutate_file_bytes(d, rec["file"], flip)
    _refresh_crc(d, m, rec["file"])
    _store_m(d, m)
    return note


def _m_trunc_torn_final(rng: random.Random, d: str) -> str:
    """Truncate a FINAL-entry file (a killed writer): recover to the
    previous complete delta — the documented torn-final contract."""
    m = _load_m(d)
    last = len(m["chain"]) - 1
    _, _, rec = _pick_rec(rng, d, m, entry=last)
    p = os.path.join(d, rec["file"])
    size = os.path.getsize(p)
    keep = rng.randrange(size)
    with open(p, "r+b") as f:
        f.truncate(keep)
    return f"{rec['file']}: truncated {size} -> {keep} bytes (final entry)"


def _m_trunc_midchain(rng: random.Random, d: str) -> str:
    """Truncate a NON-final entry's file: later deltas build on it, so
    every loader must fail loudly (never silently skip a middle link)."""
    m = _load_m(d)
    if len(m["chain"]) < 2:
        raise ValueError("mid-chain truncation needs a chain of >= 2")
    ei = rng.randrange(len(m["chain"]) - 1)
    _, _, rec = _pick_rec(rng, d, m, entry=ei)
    p = os.path.join(d, rec["file"])
    size = os.path.getsize(p)
    keep = rng.randrange(size)
    with open(p, "r+b") as f:
        f.truncate(keep)
    return f"{rec['file']}: truncated {size} -> {keep} bytes (entry {ei})"


def _zip_class(rng: random.Random, d: str,
               patch: Callable[[random.Random, bytearray], str]) -> str:
    """Shared shape of the npz structural classes: damage the zip
    structure of one chain file, then RE-STAMP its manifest crc so the
    mutation reaches the central-directory parser."""
    m = _load_m(d)
    _, _, rec = _pick_rec(rng, d, m)
    note = _mutate_file_bytes(d, rec["file"],
                              lambda buf: patch(rng, buf))
    _refresh_crc(d, m, rec["file"])
    _store_m(d, m)
    return f"{rec['file']}: {note}"


def _m_zip_name_len(rng: random.Random, d: str) -> str:
    """Oversized central-directory name_len (the PR-12 SIGSEGV shape)."""
    def patch(rng: random.Random, buf: bytearray) -> str:
        ents, _ = _central_entries(buf)
        e = ents[rng.randrange(len(ents))]
        v = rng.choice([0xEEEE, 0xFFFF, len(buf) & 0xFFFF | 0x8000])
        _p16(buf, e["nlen_off"], v)
        return f"name_len {v:#x} on member {e['name']!r}"
    return _zip_class(rng, d, patch)


def _m_zip_offset_overflow(rng: random.Random, d: str) -> str:
    """Local-header offset pointing far past the file (PR-12's uint32
    overflow shape)."""
    def patch(rng: random.Random, buf: bytearray) -> str:
        ents, _ = _central_entries(buf)
        e = ents[rng.randrange(len(ents))]
        v = rng.choice([0xFFFFFF00, 0x7FFFFFFF, len(buf) + 1])
        _p32(buf, e["lho_off"], v)
        return f"local-header offset {v:#x} on member {e['name']!r}"
    return _zip_class(rng, d, patch)


def _m_zip_zip64_marker(rng: random.Random, d: str) -> str:
    """0xFFFFFFFF zip64 markers in csize/usize/offset — the native
    reader documents zip64 as refused, not misread."""
    def patch(rng: random.Random, buf: bytearray) -> str:
        ents, _ = _central_entries(buf)
        e = ents[rng.randrange(len(ents))]
        field = rng.choice(["csize_off", "usize_off", "lho_off"])
        _p32(buf, e[field], 0xFFFFFFFF)
        return f"zip64 marker in {field[:-4]} of member {e['name']!r}"
    return _zip_class(rng, d, patch)


def _m_zip_method_deflate(rng: random.Random, d: str) -> str:
    """Stored->deflate method swap (central + local header): the
    dependency-free native reader must refuse, and the Python side must
    surface zipfile's confusion typed."""
    def patch(rng: random.Random, buf: bytearray) -> str:
        ents, _ = _central_entries(buf)
        e = ents[rng.randrange(len(ents))]
        _p16(buf, e["method_off"], 8)
        lho = e["lho"]
        if buf[lho:lho + 4] == b"PK\x03\x04":
            _p16(buf, lho + 8, 8)
        return f"method=deflate on member {e['name']!r}"
    return _zip_class(rng, d, patch)


def _m_zip_eocd_fuzz(rng: random.Random, d: str) -> str:
    """EOCD entry-count / central-directory-offset damage."""
    def patch(rng: random.Random, buf: bytearray) -> str:
        eocd = _eocd_offset(buf)
        which = rng.choice(["count", "cd_off", "cd_size"])
        if which == "count":
            _p16(buf, eocd + 10, rng.choice([0xFFFF, 0,
                                             _u16(buf, eocd + 10) + 7]))
        elif which == "cd_off":
            _p32(buf, eocd + 16, rng.choice([0xFFFFFF00, len(buf) + 9,
                                             rng.randrange(len(buf))]))
        else:
            _p32(buf, eocd + 12, rng.randrange(1 << 32))
        return f"EOCD {which} fuzzed"
    return _zip_class(rng, d, patch)


def _m_npy_descr_fuzz(rng: random.Random, d: str) -> str:
    """Same-length .npy header descr swaps inside npz members (key
    dtype narrowing, float widening): the readers must either refuse
    the dtype or both decode the same bytes the same way."""
    swaps = [(b"'<i8'", b"'<i2'"), (b"'<i8'", b"'<u8'"),
             (b"'<f4'", b"'<f8'"), (b"'<f4'", b"'<i4'"),
             (b"'<i4'", b"'<i2'")]
    m = _load_m(d)
    recs = list(_chain_recs(m))
    rng.shuffle(recs)
    for _, _, rec in recs:
        p = os.path.join(d, rec["file"])
        with open(p, "rb") as f:
            buf = bytearray(f.read())
        hits = [(old, new) for old, new in swaps if bytes(buf).find(old) >= 0]
        if not hits:
            continue
        old, new = hits[rng.randrange(len(hits))]
        i = bytes(buf).find(old)
        buf[i:i + len(old)] = new
        with open(p, "wb") as f:
            f.write(buf)
        _refresh_crc(d, m, rec["file"])
        _store_m(d, m)
        return (f"{rec['file']}: descr {old.decode()} -> {new.decode()}"
                f" at {i}")
    raise ValueError("no descr swap target found")


def _m_manifest_crc_swap(rng: random.Random, d: str) -> str:
    """Swap the crc32 fields of two manifest records: both files now
    fail their checksum (tear semantics, position-dependent)."""
    m = _load_m(d)
    recs = _chain_recs(m)
    if len(recs) < 2:
        raise ValueError("crc swap needs >= 2 records")
    (ai, an, a), (bi, bn, b) = rng.sample(recs, 2)
    a["crc32"], b["crc32"] = b["crc32"], a["crc32"]
    _store_m(d, m)
    return f"crc32 swap: entry{ai}/{an} <-> entry{bi}/{bn}"


def _m_manifest_seq_fuzz(rng: random.Random, d: str) -> str:
    """seq renumbering: gaps, dupes, and int64-overflow values. Gaps
    and dupes replay (entry ORDER is the contract); overflow seqs must
    be refused identically by Python bignums and native int64."""
    m = _load_m(d)
    chain = m["chain"]
    which = rng.choice(["gap", "dupe", "overflow", "negative"])
    if which == "gap":
        chain[-1]["seq"] += rng.randint(3, 9)
        m["last_seq"] = chain[-1]["seq"]
    elif which == "dupe" and len(chain) >= 2:
        chain[-1]["seq"] = chain[0]["seq"]
        m["last_seq"] = chain[-1]["seq"]
    elif which == "negative":
        chain[rng.randrange(len(chain))]["seq"] = -rng.randint(1, 99)
    else:
        which = "overflow"
        chain[rng.randrange(len(chain))]["seq"] = rng.choice(
            [10 ** 300, 2 ** 63, 1e300])
        m["last_seq"] = 10 ** 9
    _store_m(d, m)
    return f"seq {which}"


def _m_manifest_base_id_swap(rng: random.Random, d: str) -> str:
    """base_id / content_seq identity fuzz: loads must stay consistent
    (the id is lineage metadata, not row data)."""
    m = _load_m(d)
    if rng.random() < 0.5:
        m["base_id"] = "%032x" % rng.getrandbits(128)
        note = "base_id swapped"
    else:
        m["content_seq"] = int(m.get("content_seq", 0)) + rng.randint(0, 3)
        note = f"content_seq -> {m['content_seq']}"
    _store_m(d, m)
    return note


def _m_manifest_chunk_crc_corrupt(rng: random.Random, d: str) -> str:
    """Perturb one per-chunk checksum: whole-file crc still passes, the
    chunk layer must catch it in BOTH readers (tear semantics)."""
    m = _load_m(d)
    recs = [(ei, n, r) for ei, n, r in _chain_recs(m)
            if isinstance(r.get("chunk_crc"), list) and r["chunk_crc"]]
    if not recs:
        raise ValueError("no chunk_crc records")
    ei, name, rec = recs[rng.randrange(len(recs))]
    k = rng.randrange(len(rec["chunk_crc"]))
    rec["chunk_crc"][k] = int(rec["chunk_crc"][k]) ^ (1 + rng.randrange(255))
    _store_m(d, m)
    return f"entry{ei}/{name}: chunk_crc[{k}] perturbed"


def _m_payload_swap(rng: random.Random, d: str) -> str:
    """Swap the BYTES of two chain files, manifest untouched: both
    whole-file crcs must mis-match (tear semantics)."""
    m = _load_m(d)
    ei = rng.randrange(len(m["chain"]))
    names = sorted(m["chain"][ei]["vars"])
    if len(names) < 2:
        raise ValueError("payload swap needs >= 2 vars in an entry")
    fa = m["chain"][ei]["vars"][names[0]]["file"]
    fb = m["chain"][ei]["vars"][names[1]]["file"]
    pa, pb = os.path.join(d, fa), os.path.join(d, fb)
    with open(pa, "rb") as f:
        ba = f.read()
    with open(pb, "rb") as f:
        bb = f.read()
    with open(pa, "wb") as f:
        f.write(bb)
    with open(pb, "wb") as f:
        f.write(ba)
    return f"entry{ei}: swapped bytes of {fa} <-> {fb}"


def _m_payload_swap_crc_preserved(rng: random.Random, d: str) -> str:
    """Swap two chain files' bytes AND re-stamp both whole-file crcs:
    the outer checksum now PASSES on wrong payloads — only the chunk
    crcs / payload-kind checks stand between this and silently serving
    another variable's rows."""
    note = _m_payload_swap(rng, d)
    m = _load_m(d)
    for _, _, rec in _chain_recs(m):
        _refresh_crc(d, m, rec["file"])
    _store_m(d, m)
    return note + ", crcs re-stamped"


def _m_manifest_json_garbage(rng: random.Random, d: str) -> str:
    """Structural manifest damage: truncation, deep nesting, wrong
    types in load-bearing fields — every reader must refuse typed
    (structural corruption is never tear-recovered)."""
    p = os.path.join(d, MANIFEST)
    with open(p, "rb") as f:
        raw = f.read()
    variant = rng.choice(["truncate", "deep", "format", "chain_scalar",
                          "entry_scalar", "vars_scalar", "crc_str",
                          "file_nonstr", "not_json", "rec_scalar"])
    if variant == "truncate":
        with open(p, "wb") as f:
            f.write(raw[:rng.randrange(max(1, len(raw) - 1))])
    elif variant == "deep":
        n = 2000
        with open(p, "w") as f:
            f.write('{"format": 1, "chain": ' + "[" * n + "]" * n + "}")
    elif variant == "not_json":
        with open(p, "wb") as f:
            f.write(b"\x00\xffgarbage" * rng.randint(1, 99))
    else:
        m = json.loads(raw)
        if variant == "format":
            m["format"] = rng.choice([4, "one", None])
        elif variant == "chain_scalar":
            m["chain"] = rng.choice([7, "x", {"a": 1}])
        elif variant == "entry_scalar":
            m["chain"][rng.randrange(len(m["chain"]))] = rng.choice(
                [5, "entry", None, []])
        elif variant == "vars_scalar":
            m["chain"][rng.randrange(len(m["chain"]))]["vars"] = \
                rng.choice([3, "vars", [1, 2]])
        elif variant == "crc_str":
            _, _, rec = _pick_rec(rng, d, m)
            rec["crc32"] = rng.choice(["abc", None, [1]])
        elif variant == "rec_scalar":
            ei = rng.randrange(len(m["chain"]))
            vars_ = m["chain"][ei]["vars"]
            name = sorted(vars_)[rng.randrange(len(vars_))]
            vars_[name] = rng.choice([9, "rec", [1, 2, 3]])
        else:                                   # file_nonstr
            _, _, rec = _pick_rec(rng, d, m)
            rec["file"] = rng.choice([7, None, ["delta.npz"]])
        _store_m(d, m)
    return f"manifest {variant}"


def _m_meta_field_fuzz(rng: random.Random, d: str) -> str:
    """model_meta field fuzz (native-only probe: the Python loaders
    read variable geometry from their own specs, the native reader is
    the meta consumer): huge/NaN numbers must never hit float->int UB."""
    p = os.path.join(d, "model_meta")
    with open(p) as f:
        meta = json.load(f)
    variant = rng.choice(["vid_huge", "dim_bad", "vocab_bad",
                          "vars_scalar", "deep", "truncate"])
    if variant == "deep":
        n = 2000
        with open(p, "w") as f:
            f.write("[" * n + "]" * n)
        return "model_meta deep nesting"
    if variant == "truncate":
        raw = json.dumps(meta)
        with open(p, "w") as f:
            f.write(raw[:rng.randrange(max(1, len(raw) - 1))])
        return "model_meta truncated"
    if variant == "vars_scalar":
        meta["variables"] = rng.choice([5, "vars", None])
    else:
        variables = meta.get("variables") or []
        if not variables:
            raise ValueError("model_meta has no variables")
        v = variables[rng.randrange(len(variables))]
        if variant == "vid_huge":
            v["variable_id"] = rng.choice([1e300, -1e300, 2 ** 40])
        elif variant == "dim_bad":
            v["embedding_dim"] = rng.choice([-5, 1e300, 0])
        else:
            v["vocabulary_size"] = rng.choice([-1e300, 1e300, -7])
    with open(p, "w") as f:
        json.dump(meta, f)
    return f"model_meta {variant}"


CKPT_CLASSES: Dict[str, Callable[[random.Random, str], str]] = {
    "npz_bitflip": _m_npz_bitflip,
    "npz_bitflip_crc_fixed": _m_npz_bitflip_crc_fixed,
    "trunc_torn_final": _m_trunc_torn_final,
    "trunc_midchain": _m_trunc_midchain,
    "zip_name_len": _m_zip_name_len,
    "zip_offset_overflow": _m_zip_offset_overflow,
    "zip_zip64_marker": _m_zip_zip64_marker,
    "zip_method_deflate": _m_zip_method_deflate,
    "zip_eocd_fuzz": _m_zip_eocd_fuzz,
    "npy_descr_fuzz": _m_npy_descr_fuzz,
    "manifest_crc_swap": _m_manifest_crc_swap,
    "manifest_seq_fuzz": _m_manifest_seq_fuzz,
    "manifest_base_id_swap": _m_manifest_base_id_swap,
    "manifest_chunk_crc_corrupt": _m_manifest_chunk_crc_corrupt,
    "manifest_json_garbage": _m_manifest_json_garbage,
    "payload_swap": _m_payload_swap,
    "payload_swap_crc_preserved": _m_payload_swap_crc_preserved,
    "meta_field_fuzz": _m_meta_field_fuzz,
}

# model_meta is read by the NATIVE reader only (the Python loaders get
# variable geometry from the collection's own specs) — probing the
# Python side there would score its absent meta parser, not a parser.
NATIVE_ONLY_CLASSES = frozenset({"meta_field_fuzz"})


# --- wire-lane mutation classes ----------------------------------------------
# fn(rng, frame) -> (mutated_frame, note)

def _w_truncate(rng: random.Random, frame: bytes) -> Tuple[bytes, str]:
    keep = rng.randrange(len(frame))
    return frame[:keep], f"truncated {len(frame)} -> {keep} bytes"


def _w_bitflip(rng: random.Random, frame: bytes) -> Tuple[bytes, str]:
    buf = bytearray(frame)
    n = rng.randint(1, 16)
    for _ in range(n):
        i = rng.randrange(len(buf))
        buf[i] ^= 1 << rng.randrange(8)
    return bytes(buf), f"{n} bit flips"


def _w_bad_magic(rng: random.Random, frame: bytes) -> Tuple[bytes, str]:
    variant = rng.choice(["png", "no_newline", "empty", "binary_head"])
    if variant == "png":
        return b"\x89PNG\r\n" + frame, "PNG magic prepended"
    if variant == "no_newline":
        return frame.split(b"\n", 1)[0], "header line only, no newline"
    if variant == "empty":
        return b"", "empty frame"
    return bytes(rng.randrange(256) for _ in range(64)) + frame, \
        "64 random bytes prepended"


def _w_header_fuzz(rng: random.Random, frame: bytes) -> Tuple[bytes, str]:
    nl = frame.index(b"\n")
    head = json.loads(frame[:nl])
    body = frame[nl + 1:]
    variant = rng.choice(["vars_list", "shape_huge", "shape_negative",
                          "descr_garbage", "codec_bogus", "seq_str",
                          "spec_arity", "vars_missing", "shape_str"])
    if variant == "vars_list":
        head["vars"] = [1, 2, 3]
    elif variant == "vars_missing":
        del head["vars"]
    elif variant == "seq_str":
        head["seq"] = rng.choice(["x", None, [1]])
    elif variant == "codec_bogus":
        head["compress"] = rng.choice(["zstd", "nope", "zlib"])
    else:
        name = sorted(head["vars"])[rng.randrange(len(head["vars"]))]
        specs = head["vars"][name]
        spec = specs[rng.randrange(len(specs))]
        if variant == "shape_huge":
            spec[2] = [2 ** 40, 2 ** 40]
        elif variant == "shape_negative":
            spec[2] = [-8, 4]
        elif variant == "shape_str":
            spec[2] = "abc"
        elif variant == "descr_garbage":
            spec[1] = rng.choice(["not-a-dtype", 7, "<f99"])
        else:                                  # spec_arity
            del spec[rng.randrange(len(spec))]
    return json.dumps(head).encode() + b"\n" + body, f"header {variant}"


WIRE_CLASSES: Dict[str, Callable[[random.Random, bytes],
                                 Tuple[bytes, str]]] = {
    "wire_truncate": _w_truncate,
    "wire_bitflip": _w_bitflip,
    "wire_bad_magic": _w_bad_magic,
    "wire_header_fuzz": _w_header_fuzz,
}


# --- ingest-lane mutation classes --------------------------------------------
# fn(rng, src_shard, dst_shard) -> (fmt, note)

def _tfrecord_frames(raw: bytes) -> List[Tuple[int, int]]:
    """(offset, data_len) of each record frame; stops at damage."""
    out = []
    off = 0
    while off + 12 <= len(raw):
        n = struct.unpack_from("<Q", raw, off)[0]
        if off + 12 + n + 4 > len(raw):
            break
        out.append((off, n))
        off += 12 + n + 4
    return out


def _i_tfrecord_len(rng: random.Random, src: str,
                    dst: str) -> Tuple[str, str]:
    """Corrupt a record's length field; half the time re-stamp its
    masked crc32c so the framing READS but the record boundary lies."""
    from ..data import tfrecord
    with open(src, "rb") as f:
        raw = bytearray(f.read())
    frames = _tfrecord_frames(raw)
    off, n = frames[rng.randrange(len(frames))]
    newlen = rng.choice([n + 1, n * 7 + 13, (1 << 60) | n, 0])
    struct.pack_into("<Q", raw, off, newlen)
    fix = rng.random() < 0.5
    if fix:
        struct.pack_into("<I", raw, off + 8,
                         tfrecord.masked_crc(bytes(raw[off:off + 8])))
    with open(dst, "wb") as f:
        f.write(raw)
    return "tfrecord", (f"record@{off}: len {n} -> {newlen}"
                        f" ({'crc re-stamped' if fix else 'crc stale'})")


def _i_tfrecord_data(rng: random.Random, src: str,
                     dst: str) -> Tuple[str, str]:
    """Flip bits inside record DATA without touching its crc32c."""
    with open(src, "rb") as f:
        raw = bytearray(f.read())
    frames = _tfrecord_frames(raw)
    off, n = frames[rng.randrange(len(frames))]
    k = rng.randint(1, 8)
    for _ in range(k):
        i = off + 12 + rng.randrange(max(1, n))
        raw[i] ^= 1 << rng.randrange(8)
    with open(dst, "wb") as f:
        f.write(raw)
    return "tfrecord", f"record@{off}: {k} data bit flips"


def _i_tfrecord_trunc(rng: random.Random, src: str,
                      dst: str) -> Tuple[str, str]:
    """Cut the shard mid-record (a dying disk / partial copy)."""
    with open(src, "rb") as f:
        raw = f.read()
    keep = rng.randrange(1, len(raw))
    with open(dst, "wb") as f:
        f.write(raw[:keep])
    return "tfrecord", f"truncated {len(raw)} -> {keep} bytes"


def _i_tsv_garbage(rng: random.Random, src: str,
                   dst: str) -> Tuple[str, str]:
    """Raw-bytes TSV damage: binary splices, non-utf8 lines, an
    unterminated megarow — skip-and-count or die loudly, never hang."""
    with open(src, "rb") as f:
        raw = bytearray(f.read())
    variant = rng.choice(["splice", "non_utf8", "megarow", "nulls"])
    if variant == "splice":
        i = rng.randrange(len(raw))
        raw[i:i] = bytes(rng.randrange(256) for _ in range(256))
    elif variant == "non_utf8":
        raw += b"1\t" + bytes([0xC3, 0x28]) * 20 + b"\n"
    elif variant == "megarow":
        raw += b"2\t" + b"9" * 100_000        # no trailing newline
    else:
        for _ in range(32):
            raw[rng.randrange(len(raw))] = 0
    with open(dst, "wb") as f:
        f.write(raw)
    return "tsv", f"tsv {variant}"


INGEST_CLASSES: Dict[str, Callable[[random.Random, str, str],
                                   Tuple[str, str]]] = {
    "tfrecord_len_field": _i_tfrecord_len,
    "tfrecord_data_corrupt": _i_tfrecord_data,
    "tfrecord_truncate": _i_tfrecord_trunc,
    "tsv_garbage": _i_tsv_garbage,
}

LANE_OF = {}
for _n in CKPT_CLASSES:
    LANE_OF[_n] = "ckpt"
for _n in WIRE_CLASSES:
    LANE_OF[_n] = "wire"
for _n in INGEST_CLASSES:
    LANE_OF[_n] = "ingest"


# --- deadline execution ------------------------------------------------------

def _deadline_call(fn: Callable[[], Any], deadline: float
                   ) -> Tuple[str, Any]:
    """Run ``fn`` on a watchdog thread: ('ok', result) | ('raise', exc)
    | ('hang', None). A hung probe's thread is abandoned (daemon) — the
    violation is recorded and the harness moves on."""
    box: Dict[str, Any] = {}

    def run() -> None:
        try:
            box["r"] = fn()
        except BaseException as e:  # noqa: BLE001 — probe boundary
            box["e"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(deadline)
    if t.is_alive():
        return "hang", None
    if "e" in box:
        return "raise", box["e"]
    return "ok", box.get("r")


# --- native probe (subprocess) -----------------------------------------------

def _native_probe_main() -> int:
    """Subprocess entry (``python fuzz.py --native-probe`` + JSON spec
    on stdin): ctypes-load the sanitizer .so, open the dir, pull the
    probe rows, print one JSON line. stdlib-only: starts in ~50 ms, and
    a sanitizer abort/SIGSEGV kills THIS process, never the harness."""
    import ctypes
    spec = json.load(sys.stdin)
    lib = ctypes.CDLL(spec["lib"])
    lib.oe_last_error.restype = ctypes.c_char_p
    lib.oe_model_load.restype = ctypes.c_void_p
    lib.oe_model_load.argtypes = [ctypes.c_char_p]
    lib.oe_model_free.argtypes = [ctypes.c_void_p]
    lib.oe_model_version.restype = ctypes.c_int64
    lib.oe_model_version.argtypes = [ctypes.c_void_p]
    lib.oe_model_variable.restype = ctypes.c_void_p
    lib.oe_model_variable.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.oe_variable_dim.restype = ctypes.c_int
    lib.oe_variable_dim.argtypes = [ctypes.c_void_p]
    lib.oe_pull_weights.restype = ctypes.c_int
    lib.oe_pull_weights.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float)]
    m = lib.oe_model_load(spec["dir"].encode())
    if not m:
        err = (lib.oe_last_error() or b"").decode("utf-8", "replace")
        print(json.dumps({"outcome": "refuse", "error": err},
                         sort_keys=True))
        return 0
    h = hashlib.sha256()
    for v in spec["vars"]:
        var = lib.oe_model_variable(m, v["name"].encode())
        if not var:
            h.update(b"missing:" + v["name"].encode())
            continue
        dim = lib.oe_variable_dim(var)
        ids = v["ids"]
        keys = (ctypes.c_int64 * len(ids))(*ids)
        out = (ctypes.c_float * (len(ids) * dim))()
        rc = lib.oe_pull_weights(var, keys, len(ids), out)
        if rc != 0:
            err = (lib.oe_last_error() or b"").decode("utf-8", "replace")
            lib.oe_model_free(m)
            print(json.dumps({"outcome": "refuse",
                              "error": f"pull failed: {err}"},
                             sort_keys=True))
            return 0
        h.update(bytes(out))
    version = int(lib.oe_model_version(m))
    lib.oe_model_free(m)
    print(json.dumps({"outcome": "load", "version": version,
                      "digest": h.hexdigest()}, sort_keys=True))
    return 0


def _asan_preload() -> str:
    """gcc does not link the ASan runtime into shared objects — the
    probe interpreter must LD_PRELOAD it for the .so to resolve."""
    out = subprocess.run(["gcc", "-print-file-name=libasan.so"],
                         capture_output=True, text=True, check=True)
    p = out.stdout.strip()
    if not os.path.isabs(p):
        raise RuntimeError(f"libasan.so not found (gcc said {p!r})")
    return p


def probe_native(d: str, lib: str, probe_vars: List[Dict[str, Any]],
                 *, deadline: float = DEADLINE_S,
                 sanitizer: str = "") -> Dict[str, Any]:
    """Run the native reader over ``d`` in a contained subprocess.

    Returns {"outcome": "load"|"refuse"|"crash"|"hang", ...}. ``crash``
    carries the exit code and the stderr tail (the sanitizer report)."""
    env = dict(os.environ)
    env["ASAN_OPTIONS"] = "detect_leaks=0:abort_on_error=1"
    env["UBSAN_OPTIONS"] = "halt_on_error=1:print_stacktrace=1"
    if sanitizer == "asan":
        env["LD_PRELOAD"] = _asan_preload()
    spec = json.dumps({"dir": d, "lib": lib, "vars": probe_vars})
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--native-probe"],
            input=spec, capture_output=True, text=True, env=env,
            timeout=deadline)
    except subprocess.TimeoutExpired:
        return {"outcome": "hang"}
    for line in reversed(out.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                break
    return {"outcome": "crash", "exit": out.returncode,
            "stderr_tail": out.stderr[-800:]}


# --- python probes -----------------------------------------------------------

class SeedContext:
    """One trained seed checkpoint + everything the probes need: the
    collection pair (tracked writer / untracked loader), the probe id
    sets, and the native probe spec. Built once per run."""

    def __init__(self, tmp_root: str, *, vocab: int = 64, dim: int = 4,
                 steps: int = 2):
        import numpy as np
        import jax
        import jax.numpy as jnp
        from .. import EmbeddingCollection, EmbeddingSpec
        from .. import checkpoint as ckpt
        from .. import checkpoint_delta as cd
        from ..parallel.mesh import create_mesh
        self.tmp_root = tmp_root
        self.vocab, self.dim, self.steps = vocab, dim, steps
        self.seed_dir = os.path.join(tmp_root, "seed")
        mesh = create_mesh(1, 1, jax.devices()[:1])

        def make(track: bool) -> Any:
            specs = (EmbeddingSpec(name="arr", input_dim=vocab,
                                   output_dim=dim),
                     EmbeddingSpec(name="hsh", input_dim=-1,
                                   output_dim=dim, hash_capacity=256))
            coll = EmbeddingCollection(
                specs, mesh, default_optimizer={"category": "adagrad",
                                                "learning_rate": 0.1})
            if track:
                coll.enable_dirty_tracking(target_chunks=8)
            return coll

        coll = make(track=True)
        states = coll.init(jax.random.PRNGKey(0))
        ckpt.save_checkpoint(self.seed_dir, coll, states,
                             model_sign="graftfuzz-seed")
        hkeys: List[int] = []
        for i in range(steps):
            rs = np.random.RandomState(100 + i)
            idx = {"arr": jnp.asarray(
                       rs.randint(0, vocab, 16).astype(np.int32)),
                   "hsh": jnp.asarray(
                       rs.randint(0, 2 ** 20, 16).astype(np.int32))}
            rows = coll.pull(states, idx, batch_sharded=False)
            grads = {k: jnp.ones_like(v) * 0.25 for k, v in rows.items()}
            states = coll.apply_gradients(states, idx, grads,
                                          batch_sharded=False)
            info = cd.save_delta(self.seed_dir, coll, states, step=i + 1,
                                 compact_chain_len=1000,
                                 compact_bytes_ratio=1000.0)
            assert info["seq"] == i + 1, info
            hkeys.extend(int(k) for k in np.asarray(idx["hsh"]))
        self.load_coll = make(track=False)
        self.arr_ids = list(range(vocab)) + [-1, vocab, 10 ** 7]
        self.hsh_keys = sorted(set(hkeys)) + [123456789]
        self.wire_frames = self._build_frames(cd)

    def _build_frames(self, cd: Any) -> List[bytes]:
        delta = cd.read_delta(self.seed_dir)
        return [cd.encode_delta(delta),
                cd.encode_delta(delta, compress="zlib")]

    @property
    def native_vars(self) -> List[Dict[str, Any]]:
        return [{"name": "arr", "ids": self.arr_ids},
                {"name": "hsh", "ids": self.hsh_keys}]

    def digest_states(self, states: Any) -> str:
        """sha256 over the probe rows as f32 — byte-comparable with the
        native probe's pulls (the existing native tests assert exact
        equality on this same path)."""
        import numpy as np
        import jax.numpy as jnp
        h = hashlib.sha256()
        ids = np.asarray(self.arr_ids, np.int64)
        gt = np.where((ids < 0) | (ids >= self.vocab), -1, ids)
        rows = np.asarray(self.load_coll.pull(
            states, {"arr": jnp.asarray(gt.astype(np.int32))},
            batch_sharded=False, read_only=True)["arr"], np.float32)
        h.update(rows.tobytes())
        keys = np.asarray(self.hsh_keys, np.int64)
        rows = np.asarray(self.load_coll.pull(
            states, {"hsh": jnp.asarray(keys.astype(np.int32))},
            batch_sharded=False, read_only=True)["hsh"], np.float32)
        h.update(rows.tobytes())
        return h.hexdigest()


def probe_python_full(ctx: SeedContext, d: str, *,
                      deadline: float = DEADLINE_S) -> Dict[str, Any]:
    """``load_checkpoint`` + probe-row digest, deadline-bounded."""
    from .. import checkpoint as ckpt

    def go() -> Dict[str, Any]:
        info: Dict[str, Any] = {}
        states = ckpt.load_checkpoint(d, ctx.load_coll, info=info)
        return {"outcome": "load",
                "version": int(info.get("applied_seq", 0)),
                "digest": ctx.digest_states(states)}

    status, r = _deadline_call(go, deadline)
    if status == "hang":
        return {"outcome": "hang"}
    if status == "raise":
        if isinstance(r, PY_REFUSALS):
            return {"outcome": "refuse",
                    "error": f"{type(r).__name__}: {r}"}
        return {"outcome": "crash",
                "error": f"untyped {type(r).__name__}: {r}"}
    return r


def probe_python_delta(ctx: SeedContext, d: str, *,
                       deadline: float = DEADLINE_S) -> Dict[str, Any]:
    """``read_deltas_since(d, 0)`` — the catch-up stream a lagging
    replica replays. Participates in the crash/hang/typed-refusal
    oracle; its payloads are delta-domain (not whole-model rows), so
    they are digested for determinism but not cross-compared."""
    import numpy as np
    from .. import checkpoint_delta as cd

    def go() -> Dict[str, Any]:
        deltas = cd.read_deltas_since(d, 0)
        h = hashlib.sha256()
        for dl in deltas:
            h.update(str(int(dl.seq)).encode())
            for name in sorted(dl.vars):
                for field in sorted(dl.vars[name]):
                    h.update(field.encode())
                    h.update(np.asarray(dl.vars[name][field]).tobytes())
        return {"outcome": "load", "deltas": len(deltas),
                "seqs": [int(dl.seq) for dl in deltas],
                "digest": h.hexdigest()}

    status, r = _deadline_call(go, deadline)
    if status == "hang":
        return {"outcome": "hang"}
    if status == "raise":
        if isinstance(r, PY_REFUSALS):
            return {"outcome": "refuse",
                    "error": f"{type(r).__name__}: {r}"}
        return {"outcome": "crash",
                "error": f"untyped {type(r).__name__}: {r}"}
    return r


# --- oracle ------------------------------------------------------------------

def judge(outcomes: Dict[str, Dict[str, Any]]) -> List[str]:
    """The trichotomy, scored: crashes/hangs always lose; every probe
    that LOADED whole-model rows must agree with every other on
    (version, digest). Refusals are always acceptable — which reader
    refuses WHAT is pinned by the regression corpus, not here."""
    bad: List[str] = []
    for name, oc in sorted(outcomes.items()):
        if oc["outcome"] == "hang":
            bad.append(f"{name}: hang past deadline")
        elif oc["outcome"] == "crash":
            detail = oc.get("error") or (
                f"exit {oc.get('exit')}: {oc.get('stderr_tail', '')}")
            bad.append(f"{name}: crash ({detail.strip()})")
    loaders = [(n, oc) for n, oc in sorted(outcomes.items())
               if oc["outcome"] == "load" and "version" in oc
               and n != "python_delta"]
    for i in range(1, len(loaders)):
        (an, a), (bn, b) = loaders[0], loaders[i]
        if a["version"] != b["version"]:
            bad.append(f"divergence: {an} version {a['version']} != "
                       f"{bn} version {b['version']}")
        elif a["digest"] != b["digest"]:
            bad.append(f"divergence: {an} and {bn} loaded version "
                       f"{a['version']} with different row bytes")
    return bad


# --- lane drivers ------------------------------------------------------------

def fuzz_ckpt_dir(ctx: SeedContext, cls: str, rng: random.Random,
                  work_dir: str, libs: Dict[str, str], *,
                  deadline: float = DEADLINE_S
                  ) -> Tuple[str, Dict[str, Dict[str, Any]], List[str]]:
    """One ckpt-lane iteration: copy seed -> mutate -> all probes ->
    judge. Returns (note, outcomes, violations)."""
    d = os.path.join(work_dir, "mut")
    if os.path.exists(d):
        shutil.rmtree(d)
    shutil.copytree(ctx.seed_dir, d)
    note = CKPT_CLASSES[cls](rng, d)
    outcomes: Dict[str, Dict[str, Any]] = {}
    for variant, lib in sorted(libs.items()):
        outcomes[f"native_{variant}"] = probe_native(
            d, lib, ctx.native_vars, deadline=deadline,
            sanitizer=variant)
    if cls not in NATIVE_ONLY_CLASSES:
        outcomes["python_full"] = probe_python_full(ctx, d,
                                                    deadline=deadline)
        outcomes["python_delta"] = probe_python_delta(ctx, d,
                                                      deadline=deadline)
    return note, outcomes, judge(outcomes)


def fuzz_wire(ctx: SeedContext, cls: str, rng: random.Random, *,
              deadline: float = DEADLINE_S
              ) -> Tuple[str, Dict[str, Dict[str, Any]], List[str]]:
    """One wire-lane iteration: mutate a frame, decode it TWICE — each
    decode must be a Delta or a DeltaDecodeError, and the two must
    agree bit-for-bit (a nondeterministic decoder would let two
    replicas apply different rows from the same published frame)."""
    import numpy as np
    from .. import checkpoint_delta as cd

    frame = ctx.wire_frames[rng.randrange(len(ctx.wire_frames))]
    mut, note = WIRE_CLASSES[cls](rng, frame)

    def digest(delta: Any) -> str:
        h = hashlib.sha256()
        h.update(str((int(delta.seq), int(delta.step))).encode())
        for name in sorted(delta.vars):
            for field in sorted(delta.vars[name]):
                a = np.asarray(delta.vars[name][field])
                h.update(f"{name}/{field}/{a.dtype.str}/"
                         f"{a.shape}".encode())
                h.update(a.tobytes())
        return h.hexdigest()

    def decode_once() -> Dict[str, Any]:
        try:
            return {"outcome": "load",
                    "digest": digest(cd.decode_delta(mut))}
        except cd.DeltaDecodeError as e:
            return {"outcome": "refuse",
                    "error": f"DeltaDecodeError: {e}"}

    outcomes: Dict[str, Dict[str, Any]] = {}
    for k in ("decode_a", "decode_b"):
        status, r = _deadline_call(decode_once, deadline)
        if status == "hang":
            outcomes[k] = {"outcome": "hang"}
        elif status == "raise":
            outcomes[k] = {"outcome": "crash",
                           "error": f"untyped {type(r).__name__}: {r}"}
        else:
            outcomes[k] = r
    bad = [f"{k}: {oc['outcome']} ({oc.get('error', '')})"
           for k, oc in sorted(outcomes.items())
           if oc["outcome"] in ("hang", "crash")]
    a, b = outcomes["decode_a"], outcomes["decode_b"]
    if not bad and a != b:
        bad.append("wire decode is nondeterministic: two decodes of the "
                   "same frame disagree")
    return note, outcomes, bad


def fuzz_ingest(ctx: SeedContext, cls: str, rng: random.Random,
                work_dir: str, shard_src: Dict[str, str], *,
                deadline: float = DEADLINE_S
                ) -> Tuple[str, Dict[str, Dict[str, Any]], List[str]]:
    """One ingest-lane iteration: mutate a shard, stream it through
    :class:`ShardStream`. Acceptable: complete (skip-and-count) or a
    typed loud failure. Never a hang, never an untyped escape."""
    from ..data.stream import ShardStream
    from ..utils import observability

    fmt_hint = "tfrecord" if cls.startswith("tfrecord") else "tsv"
    src = shard_src[fmt_hint]
    dst = os.path.join(work_dir, os.path.basename(src))
    fmt, note = INGEST_CLASSES[cls](rng, src, dst)

    def consume() -> Dict[str, Any]:
        before = observability.GLOBAL.snapshot().get(
            "ingest_bad_rows", {}).get("count", 0)
        s = ShardStream([dst], batch_size=32, fmt=fmt, readers=1,
                        epochs=1, drop_remainder=False, name="graftfuzz")
        try:
            nrows = 0
            for batch in s:
                nrows += int(batch["label"].shape[0])
        finally:
            s.close()
        after = observability.GLOBAL.snapshot().get(
            "ingest_bad_rows", {}).get("count", 0)
        return {"outcome": "load", "rows": nrows,
                "bad_rows": int(after - before)}

    status, r = _deadline_call(consume, deadline)
    if status == "hang":
        oc: Dict[str, Any] = {"outcome": "hang"}
    elif status == "raise":
        if isinstance(r, PY_REFUSALS):
            oc = {"outcome": "refuse", "error": f"{type(r).__name__}: {r}"}
        else:
            oc = {"outcome": "crash",
                  "error": f"untyped {type(r).__name__}: {r}"}
    else:
        oc = r
    outcomes = {"stream": oc}
    bad = []
    if oc["outcome"] == "hang":
        bad.append("stream: reader hang past deadline")
    elif oc["outcome"] == "crash":
        bad.append(f"stream: crash ({oc['error']})")
    return note, outcomes, bad


# --- sanitizer builds --------------------------------------------------------

def sanitizer_libs(variants: Tuple[str, ...] = ("asan", "ubsan")
                   ) -> Dict[str, str]:
    """{'asan': .so path, 'ubsan': .so path} — built via the Makefile's
    sanitizer targets (``make -C native asan ubsan``)."""
    from ..serving import native as native_mod
    return {v: native_mod.build_library(variant=v) for v in variants}


# --- the run -----------------------------------------------------------------

def all_classes(lanes: Tuple[str, ...] = ("ckpt", "wire", "ingest")
                ) -> List[str]:
    return [n for n in list(CKPT_CLASSES) + list(WIRE_CLASSES)
            + list(INGEST_CLASSES) if LANE_OF[n] in lanes]


def run_fuzz(*, seed: int = 0, iters: Optional[int] = None,
             lanes: Tuple[str, ...] = ("ckpt", "wire", "ingest"),
             deadline: float = DEADLINE_S, tmp_root: Optional[str] = None,
             ctx: Optional[SeedContext] = None,
             libs: Optional[Dict[str, str]] = None,
             log: Optional[Callable[[str], None]] = None
             ) -> Dict[str, Any]:
    """The full deterministic run. Classes fire round-robin so
    ``iters >= len(classes)`` guarantees full coverage; fewer iters
    leaves silent classes, which the report marks and the CLI fails —
    a run that LOOKS green must have actually explored every declared
    mutation class. The report carries no wall-clock or absolute paths:
    same seed, same bytes."""
    import tempfile
    classes = all_classes(lanes)
    if iters is None:
        iters = len(classes)
    own_tmp = tmp_root is None
    if own_tmp:
        tmp_root = tempfile.mkdtemp(prefix="graftfuzz-")
    scrub_roots = [tmp_root]
    try:
        if ctx is None:
            ctx = SeedContext(os.path.join(tmp_root, "ctx"))
        scrub_roots.append(ctx.tmp_root)
        if libs is None:
            libs = sanitizer_libs() if "ckpt" in lanes else {}
        shard_src: Dict[str, str] = {}
        if "ingest" in lanes:
            from ..data.stream import write_synthetic_shards
            for fmt in ("tsv", "tfrecord"):
                sd = os.path.join(tmp_root, f"shards-{fmt}")
                paths = write_synthetic_shards(
                    sd, num_shards=1, rows_per_shard=96, fmt=fmt,
                    seed=7)
                shard_src[fmt] = paths[0]
        per_class: Dict[str, Dict[str, Any]] = {
            n: {"fired": 0, "violations": 0, "outcomes": {}}
            for n in classes}
        violations: List[Dict[str, Any]] = []
        iterations: List[Dict[str, Any]] = []
        work_dir = os.path.join(tmp_root, "work")
        os.makedirs(work_dir, exist_ok=True)
        for i in range(iters):
            cls = classes[i % len(classes)]
            rng = random.Random(f"{seed}:{i}:{cls}")
            try:
                if LANE_OF[cls] == "ckpt":
                    note, outcomes, bad = fuzz_ckpt_dir(
                        ctx, cls, rng, work_dir, libs, deadline=deadline)
                elif LANE_OF[cls] == "wire":
                    note, outcomes, bad = fuzz_wire(ctx, cls, rng,
                                                    deadline=deadline)
                else:
                    note, outcomes, bad = fuzz_ingest(
                        ctx, cls, rng, work_dir, shard_src,
                        deadline=deadline)
            except Exception as e:  # noqa: BLE001 — mutator failed
                note = f"mutator error: {type(e).__name__}: {e}"
                outcomes = {}
                bad = [f"mutator: {type(e).__name__}: {e}"]
            note = _scrub(note, scrub_roots)
            bad = [_scrub(b, scrub_roots) for b in bad]
            pc = per_class[cls]
            pc["fired"] += 1
            pc["violations"] += len(bad)
            for name, oc in outcomes.items():
                key = f"{name}:{oc['outcome']}"
                pc["outcomes"][key] = pc["outcomes"].get(key, 0) + 1
            summary = {name: oc["outcome"]
                       for name, oc in sorted(outcomes.items())}
            iterations.append({"iter": i, "class": cls, "note": note,
                               "outcomes": summary,
                               "violations": bad})
            for b in bad:
                violations.append({"iter": i, "class": cls, "detail": b})
            if log is not None:
                flag = " VIOLATION" if bad else ""
                log(f"[{i + 1:>3}/{iters}] {cls:<28} "
                    f"{'/'.join(summary.values()) or '-'}{flag}")
        silent = [n for n in classes if per_class[n]["fired"] == 0]
        report = {
            "gate": "graftfuzz",
            "seed": seed,
            "iters": iters,
            "lanes": sorted(lanes),
            "sanitizers": sorted(libs),
            "classes": per_class,
            "silent_classes": silent,
            "violations": violations,
            "iterations": iterations,
            "ok": not violations and not silent,
        }
        return report
    finally:
        if own_tmp:
            shutil.rmtree(tmp_root, ignore_errors=True)


# --- regression corpus -------------------------------------------------------
# Deterministic builders for the known-bad shapes (PR-12 crafted
# headers, graftchaos torn writes, compaction, codec refusal). The
# fixture (tests/fixtures/fuzz_corpus.py) references these by name and
# pins the EXPECTED per-reader disposition of each.

def _cb_with_rng(cls: str) -> Callable[[str], str]:
    def build(d: str) -> str:
        return CKPT_CLASSES[cls](random.Random(0), d)
    return build


def _cb_name_len(d: str) -> str:
    rng = random.Random(3)                    # picks 0xEEEE deterministically
    return _m_zip_name_len(rng, d)


def _cb_torn_final(d: str) -> str:
    """graftchaos torn_write shape: garbage mid-file in the newest
    entry (the exact damage tests/test_native_serving pins)."""
    m = _load_m(d)
    entry = m["chain"][-1]
    for name in sorted(entry["vars"]):
        p = os.path.join(d, entry["vars"][name]["file"])
        with open(p, "r+b") as f:
            f.seek(10)
            f.write(b"\xde\xad\xbe\xef")
    return f"seq {entry['seq']}: 4 garbage bytes at offset 10, all vars"


def _cb_torn_midchain(d: str) -> str:
    m = _load_m(d)
    entry = m["chain"][0]
    for name in sorted(entry["vars"]):
        os.remove(os.path.join(d, entry["vars"][name]["file"]))
    return f"seq {entry['seq']}: files deleted (mid-chain)"


def _cb_compacted(d: str) -> str:
    from .. import checkpoint_delta as cd
    out = cd.compact(d, background=False)
    assert out["compacted"], out
    return "chain compacted into the base (content_seq carries version)"


def _cb_deflated(d: str) -> str:
    """Re-write the newest arr payload DEFLATED (np.savez_compressed):
    valid bytes the Python reader handles, a codec the dependency-free
    native reader documents as refused — the canonical allowed
    divergence (refusal, never wrong rows)."""
    import io
    import numpy as np
    m = _load_m(d)
    rec = m["chain"][-1]["vars"][sorted(m["chain"][-1]["vars"])[0]]
    p = os.path.join(d, rec["file"])
    with open(p, "rb") as f:
        payload = dict(np.load(io.BytesIO(f.read())))
    bio = io.BytesIO()
    np.savez_compressed(bio, **payload)
    raw = bio.getvalue()
    with open(p, "wb") as f:
        f.write(raw)
    rec["crc32"] = int(zlib.crc32(raw))
    rec["bytes"] = len(raw)
    _store_m(d, m)
    return f"{rec['file']}: re-written deflated, crc re-stamped"


def _cb_deep_json(d: str) -> str:
    n = 2000
    with open(os.path.join(d, MANIFEST), "w") as f:
        f.write('{"format": 1, "chain": ' + "[" * n + "]" * n + "}")
    return "manifest chain nested 2000 deep"


def _cb_chunk_crc(d: str) -> str:
    m = _load_m(d)
    rec = m["chain"][-1]["vars"]["arr"]
    rec["chunk_crc"][0] = int(rec["chunk_crc"][0]) ^ 0xA5
    _store_m(d, m)
    return "final arr chunk_crc[0] perturbed"


def _cb_payload_swap_crc_preserved(d: str) -> str:
    m = _load_m(d)
    entry = m["chain"][-1]
    names = sorted(entry["vars"])
    fa = entry["vars"][names[0]]["file"]
    fb = entry["vars"][names[1]]["file"]
    pa, pb = os.path.join(d, fa), os.path.join(d, fb)
    with open(pa, "rb") as f:
        ba = f.read()
    with open(pb, "rb") as f:
        bb = f.read()
    with open(pa, "wb") as f:
        f.write(bb)
    with open(pb, "wb") as f:
        f.write(ba)
    _refresh_crc(d, m, fa)
    _refresh_crc(d, m, fb)
    _store_m(d, m)
    return f"final entry: {fa} <-> {fb} bytes swapped, crcs re-stamped"


def _cb_seq_overflow(d: str) -> str:
    m = _load_m(d)
    m["chain"][-1]["seq"] = 10 ** 300
    _store_m(d, m)
    return "final seq = 1e300 (past int64)"


CORPUS_BUILDERS: Dict[str, Callable[[str], str]] = {
    "name_len_overflow": _cb_name_len,
    "offset_overflow": _cb_with_rng("zip_offset_overflow"),
    "zip64_marker": _cb_with_rng("zip_zip64_marker"),
    "deflate_refusal": _cb_deflated,
    "torn_final": _cb_torn_final,
    "torn_midchain": _cb_torn_midchain,
    "compacted_dir": _cb_compacted,
    "deep_json_manifest": _cb_deep_json,
    "chunk_crc_corrupt": _cb_chunk_crc,
    "payload_swap_crc_preserved": _cb_payload_swap_crc_preserved,
    "seq_int64_overflow": _cb_seq_overflow,
}


def build_corpus_dir(name: str, ctx: SeedContext, work_dir: str) -> str:
    """Materialize corpus entry ``name`` as a fresh mutated copy of the
    seed dir; returns the directory path."""
    d = os.path.join(work_dir, f"corpus-{name}")
    if os.path.exists(d):
        shutil.rmtree(d)
    shutil.copytree(ctx.seed_dir, d)
    CORPUS_BUILDERS[name](d)
    return d


def _check_disposition(reader: str, oc: Dict[str, Any],
                       want: Dict[str, Any]) -> Optional[str]:
    if oc["outcome"] != want["outcome"]:
        return (f"{reader}: got {oc['outcome']} "
                f"({oc.get('error', '')}), pinned {want['outcome']}")
    if want["outcome"] == "refuse":
        if want["match"].lower() not in oc.get("error", "").lower():
            return (f"{reader}: refusal {oc.get('error', '')!r} does not "
                    f"match pinned substring {want['match']!r}")
    else:
        if "version" in want and oc.get("version") != want["version"]:
            return (f"{reader}: loaded version {oc.get('version')}, "
                    f"pinned {want['version']}")
        if "deltas" in want and oc.get("deltas") != want["deltas"]:
            return (f"{reader}: {oc.get('deltas')} deltas, "
                    f"pinned {want['deltas']}")
        if "seqs" in want and oc.get("seqs") != want["seqs"]:
            return (f"{reader}: seqs {oc.get('seqs')}, "
                    f"pinned {want['seqs']}")
    return None


def run_regress(ctx: SeedContext, libs: Dict[str, str], work_dir: str, *,
                deadline: float = DEADLINE_S,
                log: Optional[Callable[[str], None]] = None
                ) -> Dict[str, Any]:
    """Every corpus entry through all three readers; each must produce
    EXACTLY its pinned disposition (refusal substring or
    load/recover-to version). The corpus is how fuzzer-found bugs stay
    fixed: each fix lands with its triggering shape pinned here."""
    import importlib.util
    from ..serving import native as native_mod
    fixture_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "tests", "fixtures",
        "fuzz_corpus.py")
    spec = importlib.util.spec_from_file_location("_graftfuzz_corpus",
                                                  fixture_path)
    fuzz_corpus = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fuzz_corpus)
    failures: List[Dict[str, str]] = []
    checked = 0
    plain_lib = native_mod.build_library()
    for entry in fuzz_corpus.iter_corpus():
        name = entry["name"]
        if name not in CORPUS_BUILDERS:
            failures.append({"entry": name,
                             "detail": "unknown corpus builder"})
            continue
        d = build_corpus_dir(name, ctx, work_dir)
        expect = entry["expect"]
        outcomes = {
            "python_full": probe_python_full(ctx, d, deadline=deadline),
            "python_delta": probe_python_delta(ctx, d, deadline=deadline),
        }
        # the pinned native disposition must hold under every build —
        # plain, ASan and UBSan (the sanitizer matrix)
        native_runs = [("native[plain]", plain_lib, "")]
        native_runs += [(f"native[{v}]", libs[v], v) for v in sorted(libs)]
        for label, lib, sanitizer in native_runs:
            oc = probe_native(d, lib, ctx.native_vars, deadline=deadline,
                              sanitizer=sanitizer)
            bad = _check_disposition(label, oc, expect["native"])
            if bad:
                failures.append({"entry": name,
                                 "detail": _scrub(bad, [ctx.tmp_root, d])})
        for reader in ("python_full", "python_delta"):
            bad = _check_disposition(reader, outcomes[reader],
                                     expect[reader])
            if bad:
                failures.append({"entry": name,
                                 "detail": _scrub(bad, [ctx.tmp_root, d])})
        checked += 1
        if log is not None:
            n_bad = sum(1 for f in failures if f["entry"] == name)
            log(f"corpus {name:<28} "
                f"{'FAIL' if n_bad else 'ok'} ({entry['why']})")
    return {"gate": "graftfuzz-regress", "entries": checked,
            "failures": failures, "ok": not failures}


if __name__ == "__main__":
    if "--native-probe" in sys.argv:
        sys.exit(_native_probe_main())
    sys.stderr.write("run the harness via: python -m tools.graftfuzz\n")
    sys.exit(2)

"""Pinned regression corpus for the graftfuzz gate — known-bad shapes.

Companion of ``openembedding_tpu/analysis/fuzz.py`` (which owns the
deterministic BUILDERS, keyed by ``name`` in ``CORPUS_BUILDERS``): each
entry here pins the EXPECTED per-reader disposition of one known-bad
checkpoint shape — the PR-12 crafted npz headers (name_len SIGSEGV,
uint32 local-header-offset overflow), graftchaos torn writes (torn
final entry, mid-chain hole), the compacted-dir version contract, the
native deflate/zip64 codec refusals, crc-valid-but-wrong payloads and
the int64 seq-overflow parity case. ``python -m tools.graftfuzz
--regress`` and the tier-1 pytest lane replay every entry through all
three readers (Python loader, Python delta reader, native reader under
plain + ASan + UBSan builds) and fail unless each produces EXACTLY its
pinned disposition. This is how fuzzer-found bugs STAY fixed: each fix
lands with its triggering shape pinned here.

Disposition grammar, per reader (``python_full`` / ``python_delta`` /
``native`` — the native pin must hold under every build variant):

* ``{"outcome": "refuse", "match": <substring>}`` — typed refusal whose
  message contains ``match`` (case-insensitive).
* ``{"outcome": "load", ...}`` — loads; ``version`` pins the replayed
  seq for the loaders, ``deltas``/``seqs`` pin the delta reader's view.

Pure data, stdlib-only, loaded standalone by the CLI (no package
import) — same fixture discipline as ``graftproto_violations.py``: the
iterator VALIDATES each entry and refuses the fixture loudly when one
is malformed, so a typo'd pin can never silently pass.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List

_READERS = ("python_full", "python_delta", "native")
_REQUIRED = ("name", "expect", "why")
_OUTCOMES = ("load", "refuse")

CORPUS: List[Dict[str, Any]] = [
    {
        # PR-12's crafted central-directory name_len (the original
        # native SIGSEGV): the native reader must refuse at the central
        # directory; Python's zipfile tolerates THIS shape (the damaged
        # length field sits where truncation ends the scan) and
        # recovers the identical rows — an allowed refusal divergence,
        # never a wrong-rows divergence.
        "name": "name_len_overflow",
        "expect": {
            "python_full": {"outcome": "load", "version": 2},
            "python_delta": {"outcome": "load", "deltas": 2},
            "native": {"outcome": "refuse",
                       "match": "corrupt npz central directory"},
        },
        "why": "PR-12 crafted name_len read past the central directory "
               "(native SIGSEGV before the bounds fix)",
    },
    {
        # PR-12's uint32 local-header-offset overflow: both sides must
        # refuse typed (native bounds-checks the offset, Python wraps
        # zipfile's BadZipFile into DeltaDecodeError).
        "name": "offset_overflow",
        "expect": {
            "python_full": {"outcome": "refuse",
                            "match": "npz is unparseable"},
            "python_delta": {"outcome": "refuse",
                             "match": "npz is unparseable"},
            "native": {"outcome": "refuse",
                       "match": "corrupt npz local header"},
        },
        "why": "PR-12 uint32 offset overflow jumped the local-header "
               "read far past the mapping",
    },
    {
        # 0xFFFFFFFF size marker: zip64 is documented as REFUSED by the
        # dependency-free native reader, never misread as 4 GiB.
        "name": "zip64_marker",
        "expect": {
            "python_full": {"outcome": "refuse",
                            "match": "npz is unparseable"},
            "python_delta": {"outcome": "refuse",
                             "match": "npz is unparseable"},
            "native": {"outcome": "refuse",
                       "match": "zip64 npz member unsupported"},
        },
        "why": "zip64 markers must hit the documented refusal, not the "
               "size arithmetic",
    },
    {
        # Deflated npz members are valid bytes the Python readers
        # handle; the native reader serves mmap'd stored entries only
        # and documents deflate as refused — the canonical ALLOWED
        # divergence (a refusal, never wrong rows).
        "name": "deflate_refusal",
        "expect": {
            "python_full": {"outcome": "load", "version": 2},
            "python_delta": {"outcome": "load", "deltas": 2},
            "native": {"outcome": "refuse",
                       "match": "deflated npz member"},
        },
        "why": "codec support asymmetry must surface as a native "
               "refusal, never as divergent rows",
    },
    {
        # graftchaos torn_write, FINAL entry: the documented recovery
        # contract — loaders drop the torn entry WHOLE and serve the
        # last complete delta; the publisher refuses to ship bytes that
        # fail their checksum.
        "name": "torn_final",
        "expect": {
            "python_full": {"outcome": "load", "version": 1},
            "python_delta": {"outcome": "refuse", "match": "checksum"},
            "native": {"outcome": "load", "version": 1},
        },
        "why": "torn FINAL entry recovers to the previous complete "
               "delta in BOTH loaders (graftchaos torn_write contract)",
    },
    {
        # graftchaos torn_write, MID-chain: later deltas build on the
        # hole, so every reader must fail loudly — recovery here would
        # serve rows with a missing update in the middle.
        "name": "torn_midchain",
        "expect": {
            "python_full": {"outcome": "refuse",
                            "match": "torn mid-chain"},
            "python_delta": {"outcome": "refuse",
                             "match": "no such file"},
            "native": {"outcome": "refuse", "match": "torn mid-chain"},
        },
        "why": "a mid-chain hole must never be skipped over "
               "(silent-loss shape from the graftchaos fault matrix)",
    },
    {
        # Compacted dir: the chain is folded into the base, the
        # manifest chain is empty — content_seq must keep reporting the
        # true version (the graftproto compact_zero_version regression)
        # and the delta reader correctly has nothing left to publish.
        "name": "compacted_dir",
        "expect": {
            "python_full": {"outcome": "load", "version": 2},
            "python_delta": {"outcome": "load", "deltas": 0},
            "native": {"outcome": "load", "version": 2},
        },
        "why": "compaction burns the chain but not the version "
               "(content_seq carries it across the fold)",
    },
    {
        # 2000-deep JSON nesting: the native parser caps recursion
        # depth (stack overflow before the fix); the Python readers bound
        # it themselves before json.loads runs — what the interpreter's
        # parser does at that depth varies by version (3.12 parses it).
        "name": "deep_json_manifest",
        "expect": {
            "python_full": {"outcome": "refuse",
                            "match": "JSON nesting depth 2001 exceeds the limit 64"},
            "python_delta": {"outcome": "refuse",
                             "match": "JSON nesting depth 2001 exceeds the limit 64"},
            "native": {"outcome": "refuse", "match": "not valid JSON"},
        },
        "why": "deep nesting must exhaust a BOUNDED parser depth, "
               "never the native stack (C-stack overflow shape)",
    },
    {
        # One per-chunk checksum perturbed, whole-file crc intact: the
        # chunk layer must catch it in BOTH loaders (native ignored
        # chunk_crc entirely before this gate) and tear back to seq 1;
        # the delta reader serves the crc-valid file bytes untouched —
        # its whole-file checksum genuinely passes.
        "name": "chunk_crc_corrupt",
        "expect": {
            "python_full": {"outcome": "load", "version": 1},
            "python_delta": {"outcome": "load", "deltas": 2,
                             "seqs": [1, 2]},
            "native": {"outcome": "load", "version": 1},
        },
        "why": "chunk checksums must be VERIFIED, not just stored "
               "(native skipped them before this gate)",
    },
    {
        # Two payload files' bytes swapped AND their manifest crcs
        # re-stamped: the whole-file checksum now passes on wrong
        # payloads — only the chunk-crc/payload-kind layer stands
        # between this and serving another variable's rows.
        "name": "payload_swap_crc_preserved",
        "expect": {
            "python_full": {"outcome": "load", "version": 1},
            "python_delta": {"outcome": "load", "deltas": 2},
            "native": {"outcome": "load", "version": 1},
        },
        "why": "crc-PRESERVING payload swap: the inner integrity layer "
               "must tear, or wrong rows serve with a green checksum",
    },
    {
        # seq = 1e300: Python bignums would happily replay to version
        # 10^300 while native int64 refuses — the _seq_ok parity guard
        # makes BOTH refuse structurally (divergence shape found by the
        # fuzzer's manifest_json_garbage class during development).
        "name": "seq_int64_overflow",
        "expect": {
            "python_full": {"outcome": "refuse",
                            "match": "corrupt delta chain entry"},
            "python_delta": {"outcome": "refuse",
                             "match": "corrupt delta chain"},
            "native": {"outcome": "refuse",
                       "match": "corrupt delta chain entry"},
        },
        "why": "a past-int64 seq must refuse in BOTH readers — Python "
               "bignums vs native int64 was a silent version-divergence "
               "shape",
    },
]


def iter_corpus() -> Iterator[Dict[str, Any]]:
    """Validated iteration — malformed entries fail the whole fixture.

    A corpus entry whose expectation is missing or mistyped would
    otherwise pass vacuously; this mirrors ``graftproto_violations``'
    fixture discipline (reject, never skip)."""
    seen = set()
    for i, entry in enumerate(CORPUS):
        if not isinstance(entry, dict):
            raise ValueError(f"corpus[{i}] is not a dict")
        missing = [k for k in _REQUIRED if k not in entry]
        if missing:
            raise ValueError(f"corpus[{i}] missing keys {missing}")
        unknown = [k for k in entry if k not in _REQUIRED]
        if unknown:
            raise ValueError(f"corpus[{i}] unknown keys {unknown}")
        name = entry["name"]
        if name in seen:
            raise ValueError(f"corpus[{i}] duplicate name {name!r}")
        seen.add(name)
        expect = entry["expect"]
        if not isinstance(expect, dict) or \
                sorted(expect) != sorted(_READERS):
            raise ValueError(
                f"corpus[{i}] ({name}): expect must pin exactly "
                f"{_READERS}, got {sorted(expect) if isinstance(expect, dict) else expect}")
        for reader, want in expect.items():
            if not isinstance(want, dict) or \
                    want.get("outcome") not in _OUTCOMES:
                raise ValueError(
                    f"corpus[{i}] ({name}): {reader} outcome must be "
                    f"one of {_OUTCOMES}")
            if want["outcome"] == "refuse" and not want.get("match"):
                raise ValueError(
                    f"corpus[{i}] ({name}): {reader} refusal pins no "
                    f"'match' substring — a vacuous expectation")
        if not entry["why"]:
            raise ValueError(f"corpus[{i}] ({name}): empty why")
        yield entry

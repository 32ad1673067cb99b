"""graftfuzz gate: differential fuzzing + sanitizer coverage (tier 1).

Four layers, mirroring the gate's own structure:

* the pinned regression corpus (``tests/fixtures/fuzz_corpus.py``) —
  every known-bad checkpoint shape must produce EXACTLY its pinned
  disposition through all three readers, with the native reader probed
  under BOTH the plain and the ASan-instrumented build (each probe in
  its own subprocess). This is also the tier-1 coverage for native
  refusal paths no Python test could previously reach: the deflate and
  zip64 refusal messages, the crafted name_len central-directory
  refusal, the mid-chain tear, and ``oe_model_version`` on a compacted
  chain.
* ``DeltaDecodeError`` surfacing — truncated / bit-flipped /
  wrong-magic wire frames refuse typed from ``decode_delta``, and the
  REST ``POST /models/<sign>/delta`` handler maps that refusal to 400
  (never a 500 from a raw ``struct.error``/``zlib.error``).
* harness determinism — two wire-lane runs with the same seed produce
  byte-identical reports, and the full class list is declared.
* the ingest lane — mutated TFRecord/TSV shards through ShardStream
  must skip-and-count or fail loudly within the deadline, never hang.

The heavier randomized sweep runs in CI (`python -m tools.graftfuzz`,
per-PR fixed-seed smoke + weekly randomized long run), not here.
"""

import http.client
import importlib.util
import json
import os
import shutil
import subprocess

import numpy as np
import pytest

import jax

from openembedding_tpu import checkpoint_delta as cd
from openembedding_tpu.analysis import fuzz
from openembedding_tpu.serving import native as native_mod

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "fuzz_corpus.py")

pytestmark = pytest.mark.skipif(shutil.which("g++") is None,
                                reason="native toolchain (g++) required")


def _load_fixture():
    spec = importlib.util.spec_from_file_location("fuzz_corpus_fixture",
                                                  FIXTURE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    return fuzz.SeedContext(str(tmp_path_factory.mktemp("graftfuzz")))


@pytest.fixture(scope="module")
def libs():
    # plain + ASan: the sanitizer leg of the matrix that tier 1 pays
    # for; the UBSan leg rides in the CI smoke (tools/graftfuzz.py)
    return {"": native_mod.build_library(),
            "asan": native_mod.build_library(variant="asan")}


CORPUS_NAMES = [e["name"] for e in _load_fixture().iter_corpus()]


# --- the pinned corpus, through all three readers ---------------------------

@pytest.mark.parametrize("name", CORPUS_NAMES)
def test_corpus_disposition(ctx, libs, tmp_path, name):
    """Each known-bad shape produces exactly its pinned disposition —
    refusal-message substring or recover-to version — in the Python
    loader, the Python delta reader, and the native reader under both
    the plain and the ASan build."""
    entry = next(e for e in _load_fixture().iter_corpus()
                 if e["name"] == name)
    d = fuzz.build_corpus_dir(name, ctx, str(tmp_path))
    expect = entry["expect"]
    failures = []
    for variant, lib in sorted(libs.items()):
        oc = fuzz.probe_native(d, lib, ctx.native_vars, sanitizer=variant)
        bad = fuzz._check_disposition(f"native[{variant or 'plain'}]",
                                      oc, expect["native"])
        if bad:
            failures.append(bad)
    for reader, probe in (("python_full", fuzz.probe_python_full),
                          ("python_delta", fuzz.probe_python_delta)):
        oc = probe(ctx, d)
        bad = fuzz._check_disposition(reader, oc, expect[reader])
        if bad:
            failures.append(bad)
    assert not failures, f"{name} ({entry['why']}): {failures}"


def test_corpus_fixture_rejects_malformed():
    """The iterator refuses malformed entries instead of skipping them
    (a typo'd pin must fail the fixture, never pass vacuously)."""
    mod = _load_fixture()
    good = dict(next(mod.iter_corpus()))

    def with_corpus(entries):
        mod.CORPUS = entries
        return list(mod.iter_corpus())

    orig = list(mod.CORPUS)
    try:
        for broken, why in [
            ({k: v for k, v in good.items() if k != "expect"}, "missing"),
            (dict(good, bogus=1), "unknown key"),
            (dict(good, expect={"python_full": good["expect"][
                "python_full"]}), "incomplete readers"),
            (dict(good, expect=dict(
                good["expect"],
                native={"outcome": "refuse"})), "refusal without match"),
            (dict(good, expect=dict(
                good["expect"],
                native={"outcome": "explode"})), "bad outcome"),
        ]:
            with pytest.raises(ValueError):
                with_corpus([broken])
        with pytest.raises(ValueError):
            with_corpus([good, dict(good)])     # duplicate name
    finally:
        mod.CORPUS = orig


# --- native refusal paths unreachable from the Python bindings --------------

def test_native_truncated_member_refusal(ctx, libs, tmp_path):
    """A stored member whose data runs past the mapping must refuse
    ("truncated npz member"), not read out of bounds — asserted under
    ASan, where an over-read would abort the probe."""
    d = os.path.join(str(tmp_path), "d")
    shutil.copytree(ctx.seed_dir, d)
    m = fuzz._load_m(d)
    rec = m["chain"][-1]["vars"]["arr"]
    p = os.path.join(d, rec["file"])
    with open(p, "rb") as f:
        buf = bytearray(f.read())
    ents, _ = fuzz._central_entries(buf)
    # grow the last member's sizes past EOF, keep the zip walkable
    e = max(ents, key=lambda x: x["lho"])
    grow = len(buf)
    fuzz._p32(buf, e["csize_off"], fuzz._u32(buf, e["csize_off"]) + grow)
    fuzz._p32(buf, e["usize_off"], fuzz._u32(buf, e["usize_off"]) + grow)
    lho = e["lho"]
    assert buf[lho:lho + 4] == b"PK\x03\x04"
    fuzz._p32(buf, lho + 18, fuzz._u32(buf, lho + 18) + grow)
    fuzz._p32(buf, lho + 22, fuzz._u32(buf, lho + 22) + grow)
    with open(p, "wb") as f:
        f.write(buf)
    fuzz._refresh_crc(d, m, rec["file"])
    fuzz._store_m(d, m)
    oc = fuzz.probe_native(d, libs["asan"], ctx.native_vars,
                           sanitizer="asan")
    assert oc["outcome"] == "refuse", oc
    assert "truncated npz member" in oc["error"], oc


def test_native_key_dtype_refusal(ctx, libs, tmp_path):
    """Narrowing a hash payload's KEY descr ('<i4' -> '<i2') must hit
    the typed dtype refusal, not reinterpret the key bytes (the
    garbage-read shape the keys_dtype guard closed). keys.npy is the
    only '<i4' member of an hsh delta (weights/accums are '<f4',
    chunk ids '<i8')."""
    d = os.path.join(str(tmp_path), "d")
    shutil.copytree(ctx.seed_dir, d)
    m = fuzz._load_m(d)
    hit = None
    for _, name, rec in fuzz._chain_recs(m):
        if name != "hsh":
            continue
        p = os.path.join(d, rec["file"])
        with open(p, "rb") as f:
            buf = bytearray(f.read())
        i = bytes(buf).find(b"'<i4'")
        if i < 0:
            continue
        buf[i:i + 5] = b"'<i2'"
        with open(p, "wb") as f:
            f.write(buf)
        fuzz._refresh_crc(d, m, rec["file"])
        hit = rec["file"]
        break
    assert hit, "no '<i4' key descr found in any hsh payload"
    fuzz._store_m(d, m)
    oc = fuzz.probe_native(d, libs["asan"], ctx.native_vars,
                           sanitizer="asan")
    assert oc["outcome"] == "refuse", oc
    assert "dtype" in oc["error"], oc


# --- DeltaDecodeError surfacing ---------------------------------------------

def _frame(ctx):
    return ctx.wire_frames[0]


def test_decode_delta_truncated_refuses_typed(ctx):
    frame = _frame(ctx)
    for keep in (0, 1, len(frame) // 2, len(frame) - 1):
        with pytest.raises(cd.DeltaDecodeError) as ei:
            cd.decode_delta(frame[:keep])
        assert str(ei.value)        # carries context, never empty

def test_decode_delta_bitflip_refuses_or_roundtrips(ctx):
    """Bit flips anywhere in the frame either refuse typed or decode
    deterministically — decode_delta never raises anything but
    DeltaDecodeError (struct.error/zlib.error escaping raw was the
    pre-gate behavior)."""
    frame = _frame(ctx)
    rng = np.random.RandomState(0)
    for _ in range(64):
        buf = bytearray(frame)
        i = int(rng.randint(len(buf)))
        buf[i] ^= 1 << int(rng.randint(8))
        try:
            d1 = cd.decode_delta(bytes(buf))
            d2 = cd.decode_delta(bytes(buf))
        except cd.DeltaDecodeError:
            continue
        assert d1.seq == d2.seq and sorted(d1.vars) == sorted(d2.vars)


def test_decode_delta_wrong_magic_refuses_typed(ctx):
    for garbage in (b"\x89PNG\r\n" + _frame(ctx), b"PK\x03\x04etc",
                    b"", b"\x00" * 64,
                    b'{"seq": 1}'):            # header but no newline
        with pytest.raises(cd.DeltaDecodeError):
            cd.decode_delta(garbage)


def test_decode_delta_error_is_valueerror():
    """The REST mapping contract: DeltaDecodeError IS a ValueError, so
    the handler's existing (KeyError, ValueError) -> 400 arm covers
    corrupt frames with no rest.py special case."""
    assert issubclass(cd.DeltaDecodeError, ValueError)


def test_rest_delta_post_corrupt_body_maps_400(devices8, tmp_path):
    """End to end over HTTP: a corrupt delta POST answers 400 (typed
    refusal), a valid frame still applies (200) — the fuzzer's REST
    surfacing satellite."""
    from openembedding_tpu import (EmbeddingCollection, EmbeddingSpec,
                                   checkpoint as ckpt)
    from openembedding_tpu.parallel.mesh import create_mesh
    from openembedding_tpu.serving.registry import ModelRegistry
    from openembedding_tpu.serving.rest import ControllerServer
    vocab, dim = 32, 4
    mesh = create_mesh(2, 4, devices8)
    coll = EmbeddingCollection(
        (EmbeddingSpec(name="arr", input_dim=vocab, output_dim=dim),),
        mesh)
    states = coll.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "model")
    ckpt.save_checkpoint(path, coll, states, model_sign="fz-1")
    reg = ModelRegistry(mesh)
    reg.create_model(path, block=True)
    srv = ControllerServer(reg, port=0).start()
    try:
        c = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)

        def post(body):
            c.request("POST", "/models/fz-1/delta", body)
            r = c.getresponse()
            return r.status, json.loads(r.read() or b"null")

        good = cd.encode_delta(cd.Delta(seq=1, step=1, vars={"arr": {
            "weights": np.full((vocab, dim), 2.0, np.float32),
            "chunks": np.array([0], np.int64),
            "rows_per_chunk": np.array(vocab, np.int64),
            "vocab": np.array(vocab, np.int64),
        }}))
        for corrupt in (good[: len(good) // 2],      # truncated body
                        b"\x89PNG\r\n" + good,       # wrong magic
                        good.split(b"\n", 1)[0]):    # header, no body
            code, obj = post(corrupt)
            assert code == 400, (code, obj)
        buf = bytearray(good)
        buf[len(buf) - 8] ^= 0x40                    # payload bit flip
        code, obj = post(bytes(buf))
        assert code in (200, 400), (code, obj)
        code, obj = post(good)
        if code == 200:                              # not already applied
            assert obj["version"] == 1
        code, obj = post(good[:0])                   # empty body
        assert code == 400, (code, obj)
    finally:
        srv.stop()
        reg.close()


# --- harness determinism + coverage accounting ------------------------------

def test_wire_lane_deterministic_and_covered(ctx):
    """Two same-seed wire-lane runs produce byte-identical reports,
    every wire class fires, zero violations; a short run leaves the
    unfired classes marked silent (ok=False)."""
    kw = dict(seed=7, lanes=("wire",), ctx=ctx, libs={})
    a = fuzz.run_fuzz(**kw)
    b = fuzz.run_fuzz(**kw)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert a["ok"], a["violations"] or a["silent_classes"]
    assert sorted(a["classes"]) == sorted(fuzz.WIRE_CLASSES)
    assert all(c["fired"] for c in a["classes"].values())
    short = fuzz.run_fuzz(seed=7, iters=1, lanes=("wire",), ctx=ctx,
                          libs={})
    assert short["silent_classes"] and not short["ok"]


def test_declared_classes_span_all_lanes():
    names = fuzz.all_classes()
    assert set(names) == (set(fuzz.CKPT_CLASSES) | set(fuzz.WIRE_CLASSES)
                          | set(fuzz.INGEST_CLASSES))
    assert len(names) >= 24     # the declared mutator grammar floor
    assert fuzz.NATIVE_ONLY_CLASSES <= set(fuzz.CKPT_CLASSES)


# --- the ingest lane ---------------------------------------------------------

def test_ingest_lane_skips_or_fails_loudly(ctx):
    """Every ingest mutation class: the mutated shard either streams to
    completion (damage skipped AND counted) or dies with a typed error
    — never a hang, never an untyped escape, pool still usable."""
    report = fuzz.run_fuzz(seed=3, lanes=("ingest",), ctx=ctx, libs={},
                           deadline=60.0)
    assert report["ok"], (report["violations"]
                          or report["silent_classes"])
    assert sorted(report["classes"]) == sorted(fuzz.INGEST_CLASSES)
    outcomes = {k for c in report["classes"].values()
                for k in c["outcomes"]}
    assert outcomes <= {"stream:load", "stream:refuse"}, outcomes

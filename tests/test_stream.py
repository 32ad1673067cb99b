"""Streaming ingest: shard pool determinism, bad-row/crash lanes, ring
bounds + ledger, and stall accounting."""

import threading
import time
import warnings

import numpy as np
import pytest

from openembedding_tpu.data import criteo, stream, tfrecord
from openembedding_tpu.utils import observability


def _shards(tmp_path, **kw):
    d = str(tmp_path / "shards")
    kw.setdefault("num_shards", 4)
    kw.setdefault("rows_per_shard", 512)
    return d, stream.write_synthetic_shards(d, **kw)


# --- synthetic source --------------------------------------------------------

def test_synthetic_shards_are_real_criteo_tsv(tmp_path):
    """The generated shards parse through the PORTABLE reference reader
    (same row grammar as raw Criteo TSV) and carry zipf-skewed ids."""
    d, paths = _shards(tmp_path, num_shards=2, rows_per_shard=600, seed=3)
    assert [p.endswith(".tsv") for p in paths] == [True, True]
    batches = list(criteo.read_criteo_tsv(paths[0], 100,
                                          num_buckets=1 << 16))
    assert len(batches) == 6
    b = batches[0]
    assert b["dense"].shape == (100, criteo.NUM_DENSE)
    assert set(b["sparse"]) == set(criteo.SPARSE_NAMES)
    # zipf marginals: the top key of a column owns far more than a
    # uniform draw would (600 rows over 2^16 buckets ~ all-unique)
    col = np.concatenate([bb["sparse"]["C1"] for bb in batches])
    _, counts = np.unique(col, return_counts=True)
    assert counts.max() >= 20   # zipf(1.2): id 1 alone is ~35% of draws
    # deterministic per (seed, shard)
    d2 = str(tmp_path / "again")
    paths2 = stream.write_synthetic_shards(d2, num_shards=2,
                                           rows_per_shard=600, seed=3)
    assert open(paths[1]).read() == open(paths2[1]).read()


def test_synthetic_tfrecord_shards_roundtrip(tmp_path):
    d = str(tmp_path)
    paths = stream.write_synthetic_shards(d, num_shards=1,
                                          rows_per_shard=40,
                                          fmt="tfrecord", seed=1)
    recs = list(tfrecord.read_records(paths[0]))
    assert len(recs) == 40
    ex = tfrecord.parse_example(recs[0])
    assert set(ex) == {"label"} | set(criteo.DENSE_NAMES) \
        | set(criteo.SPARSE_NAMES)


# --- determinism + parity with the reference reader --------------------------

def test_stream_deterministic_across_runs(tmp_path):
    d, _ = _shards(tmp_path, num_shards=4, rows_per_shard=300, seed=5)

    def collect():
        s = stream.ShardStream(d, batch_size=64, readers=3,
                               ring_batches=6, epochs=1,
                               num_buckets=1 << 12)
        try:
            return list(s)
        finally:
            s.close()

    a, b = collect(), collect()
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["label"], y["label"])
        np.testing.assert_array_equal(x["dense"], y["dense"])
        for n in criteo.SPARSE_NAMES:
            np.testing.assert_array_equal(x["sparse"][n], y["sparse"][n])


def test_single_reader_matches_reference_reader(tmp_path):
    """readers=1 over one shard == criteo.read_criteo_tsv exactly (the
    stream is the reference reader's parallel form, not a new format)."""
    d, paths = _shards(tmp_path, num_shards=1, rows_per_shard=500, seed=7)
    ref = list(criteo.read_criteo_tsv(paths[0], 128,
                                      num_buckets=1 << 14))
    s = stream.ShardStream(paths, batch_size=128, readers=1, epochs=1,
                           num_buckets=1 << 14)
    try:
        got = list(s)
    finally:
        s.close()
    assert len(got) == len(ref)
    for x, y in zip(got, ref):
        np.testing.assert_array_equal(x["dense"], y["dense"])
        for n in criteo.SPARSE_NAMES:
            np.testing.assert_array_equal(x["sparse"][n], y["sparse"][n])


def test_add_linear_and_transform_run_on_worker(tmp_path):
    d, _ = _shards(tmp_path, num_shards=1, rows_per_shard=128, seed=2)
    tids = []

    def xform(b):
        tids.append(threading.get_ident())
        return {**b, "tag": True}

    s = stream.ShardStream(d, batch_size=64, epochs=1, add_linear=True,
                           transform=xform, num_buckets=1 << 12)
    try:
        batches = list(s)
    finally:
        s.close()
    assert batches and all(b.get("tag") for b in batches)
    np.testing.assert_array_equal(batches[0]["sparse"]["C3"],
                                  batches[0]["sparse"]["C3:linear"])
    assert threading.get_ident() not in tids   # parsed off the consumer


# --- bad rows (satellite bugfix) ---------------------------------------------

def test_tsv_reader_skips_bad_rows_with_counter_and_warning(tmp_path):
    """The portable reader survives a corrupted shard: short lines and
    non-hex categoricals are skipped + counted (`ingest_bad_rows`),
    with one loud threshold warning — previously `int(v, 16)` crashed
    the whole stream on the first non-hex value."""
    d, paths = _shards(tmp_path, num_shards=1, rows_per_shard=500,
                       seed=1, bad_rows_per_shard=40)
    observability.GLOBAL.reset()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        batches = list(criteo.read_criteo_tsv(paths[0], 100,
                                              num_buckets=1 << 12,
                                              drop_remainder=False))
    rows = sum(b["label"].shape[0] for b in batches)
    assert rows == 500 - 40
    snap = observability.GLOBAL.snapshot()
    assert snap["ingest_bad_rows"]["count"] == 40
    # the warning fires ONCE, as soon as the cumulative bad fraction
    # crosses the threshold with >= 32 bad rows seen
    msgs = [str(x.message) for x in w
            if issubclass(x.category, RuntimeWarning)]
    assert len(msgs) == 1 and "unparseable" in msgs[0]


def test_stream_bad_rows_counted_not_fatal(tmp_path):
    d, _ = _shards(tmp_path, num_shards=2, rows_per_shard=400, seed=4,
                   bad_rows_per_shard=30)
    s = stream.ShardStream(d, batch_size=64, readers=2, epochs=1,
                           num_buckets=1 << 12)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            batches = list(s)
        assert s.bad_rows() == 60
    finally:
        s.close()
    # reader-local batching: each reader drops its own remainder
    assert sum(b["label"].shape[0] for b in batches) \
        == 2 * ((400 - 30) // 64) * 64


def test_clean_fixture_never_warns(tmp_path):
    d, paths = _shards(tmp_path, num_shards=1, rows_per_shard=64, seed=9)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        list(criteo.read_criteo_tsv(paths[0], 16, num_buckets=1 << 10))
    assert not [x for x in w if issubclass(x.category, RuntimeWarning)]


# --- reader crash / truncation lanes -----------------------------------------

def _truncate(path, frac=0.5, extra=7):
    raw = open(path, "rb").read()
    open(path, "wb").write(raw[:int(len(raw) * frac) + extra])


def test_dead_reader_fails_epoch_loudly_never_hangs(tmp_path):
    """Mid-file TFRecord truncation: the reader dies, the NEXT consumer
    pop raises (naming reader + shard) within a bounded wait — never a
    hang, never a silently short epoch — and the stream stays failed."""
    d = str(tmp_path)
    paths = stream.write_synthetic_shards(d, num_shards=2,
                                          rows_per_shard=200,
                                          fmt="tfrecord", seed=2)
    _truncate(paths[1])
    s = stream.ShardStream(d, fmt="tfrecord", batch_size=32, readers=2,
                           epochs=1, num_buckets=1 << 12)
    t0 = time.time()
    with pytest.raises(RuntimeError, match="reader 1 .* failed"):
        for _ in s:
            pass
    assert time.time() - t0 < 30
    with pytest.raises(RuntimeError, match="already failed"):
        next(s)
    s.close()


def test_short_read_header_truncation_fails(tmp_path):
    """A TFRecord cut inside the 12-byte header is container damage:
    IOError out of the frame reader -> loud epoch failure."""
    d = str(tmp_path)
    paths = stream.write_synthetic_shards(d, num_shards=1,
                                          rows_per_shard=50,
                                          fmt="tfrecord", seed=6)
    raw = open(paths[0], "rb").read()
    open(paths[0], "wb").write(raw + b"\x07\x00\x00")   # dangling header
    s = stream.ShardStream(paths, fmt="tfrecord", batch_size=16,
                           readers=1, epochs=1, drop_remainder=False,
                           num_buckets=1 << 12)
    with pytest.raises(RuntimeError, match="truncated TFRecord"):
        for _ in s:
            pass
    s.close()


def test_missing_shard_dir_fails_at_construction(tmp_path):
    with pytest.raises(FileNotFoundError):
        stream.discover_shards(str(tmp_path), "tsv")


def test_close_mid_stream_joins_readers(tmp_path):
    d, _ = _shards(tmp_path, num_shards=2, rows_per_shard=400, seed=8)
    s = stream.ShardStream(d, batch_size=32, readers=2, epochs=None,
                           ring_batches=4, num_buckets=1 << 12)
    next(s)
    s.close()
    assert all(not t.is_alive() for t in s._threads)
    with pytest.raises(StopIteration):
        next(s)


# --- ring bounds, ledger, stall accounting -----------------------------------

def test_ring_bounded_and_memory_ledger(tmp_path):
    d, _ = _shards(tmp_path, num_shards=2, rows_per_shard=600, seed=3)
    s = stream.ShardStream(d, batch_size=50, readers=2, ring_batches=4,
                           epochs=None, num_buckets=1 << 12,
                           name="ledger_test")
    try:
        next(s)
        time.sleep(0.5)   # paused consumer: readers fill to the bound
        st = s.memory_stats()
        assert st["ring_batches"] <= st["ring_capacity_batches"] == 4.0
        assert st["ring_bytes"] > 0
        # registered as an oe_mem_* source for /metrics
        mem = observability.memory_stats()
        assert "ingest/ledger_test" in mem
        assert mem["ingest/ledger_test"]["ring_capacity_batches"] == 4.0
    finally:
        s.close()


def test_stall_accounting_exact_zero_when_ready(tmp_path):
    """A pop that finds data ready records EXACTLY 0.0 (the p95==0
    claim is over literal zeros); a pop that waits records the wait."""
    d, _ = _shards(tmp_path, num_shards=1, rows_per_shard=300, seed=5)
    # slow producer: the transform sleeps on the worker
    s = stream.ShardStream(d, batch_size=100, readers=1, epochs=1,
                           num_buckets=1 << 12,
                           transform=lambda b: (time.sleep(0.05), b)[1])
    try:
        list(s)
        stalled = s.stall_summary()
        assert stalled["stalled"] >= 1 and stalled["max_ms"] > 0
    finally:
        s.close()
    # fast producer + slow consumer: zero stalls, exactly
    s2 = stream.ShardStream(d, batch_size=100, readers=1, epochs=1,
                            num_buckets=1 << 12)
    try:
        time.sleep(0.3)
        out = []
        for b in s2:
            out.append(b)
            time.sleep(0.02)
        st = s2.stall_stats()
        assert st.size == len(out) and (st == 0.0).all()
        assert s2.stall_summary()["p95_ms"] == 0.0
    finally:
        s2.close()
    # reset drops history
    s2.reset_stall_stats()
    assert s2.stall_stats().size == 0


def test_record_ingest_stall_counter_and_histogram():
    from openembedding_tpu.analysis import scope
    acc = observability.Accumulator()
    before = scope.HISTOGRAMS.count("ingest_stall_ms")
    observability.record_ingest_stall(0.002, accumulator=acc)
    observability.record_ingest_stall(0.0, accumulator=acc)
    snap = acc.snapshot()
    assert snap["ingest_stall"]["calls"] == 2
    assert abs(snap["ingest_stall"]["seconds"] - 0.002) < 1e-9
    assert scope.HISTOGRAMS.count("ingest_stall_ms") == before + 2
    assert stream.ShardStream.ingest_accounted is True

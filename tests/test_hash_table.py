"""Hash-table embedding: probe/insert correctness, reference pull/update
semantics (deferred materialization), sharded parity with the local table.

Mirrors the reference's hash-variable paths in c_api_test.h (dense/hash
matrix) — ground truth here is a Python dict replica updated with the same
deterministic rules, plus single-vs-sharded cross-checks.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu import EmbeddingVariableMeta, make_optimizer
from openembedding_tpu import hash_table as ht
from openembedding_tpu.parallel.mesh import create_mesh
from openembedding_tpu.parallel import sharded_hash as sh

DIM = 4
META = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=2**63)
INIT = {"category": "constant", "value": 0.25}


def test_meta_selects_hash():
    assert META.use_hash_table


def test_pull_missing_returns_init_and_is_deterministic():
    t = ht.create_hash_table(META, "default", capacity=64)
    keys = jnp.array([7, 123456, -5], dtype=jnp.int32)
    rows1 = ht.pull(t, keys, {"category": "uniform", "minval": -1, "maxval": 1})
    rows2 = ht.pull(t, keys, {"category": "uniform", "minval": -1, "maxval": 1})
    np.testing.assert_array_equal(np.asarray(rows1), np.asarray(rows2))
    # distinct keys get distinct init rows
    assert not np.allclose(np.asarray(rows1)[0], np.asarray(rows1)[1])


def test_insert_then_find():
    t = ht.create_hash_table(META, {"category": "sgd", "learning_rate": 1.0},
                             capacity=128)
    opt = make_optimizer({"category": "sgd", "learning_rate": 1.0})
    keys = jnp.array([3, 900001, 42, 3], dtype=jnp.int32)
    grads = jnp.ones((4, DIM), dtype=jnp.float32)
    t = ht.apply_gradients(t, opt, INIT, keys, grads)
    assert int(t.num_used()) == 3
    assert int(t.insert_failures) == 0
    # present keys now pull their stored (updated) rows: init 0.25 - lr*sum
    rows = np.asarray(ht.pull(t, jnp.array([3, 42], jnp.int32), INIT))
    np.testing.assert_allclose(rows[0], 0.25 - 2.0, rtol=1e-6)  # key 3 dup x2
    np.testing.assert_allclose(rows[1], 0.25 - 1.0, rtol=1e-6)


def test_pull_update_consistency_vs_dict_replica():
    """Random pull/push stream against a host dict replica (SGD, exact)."""
    lr = 0.5
    opt = make_optimizer({"category": "sgd", "learning_rate": lr})
    t = ht.create_hash_table(META, opt, capacity=512)
    replica = {}
    rng = np.random.RandomState(1)
    for step in range(5):
        keys = rng.randint(0, 10**9, size=32).astype(np.int32)
        grads = rng.randn(32, DIM).astype(np.float32)
        jk, jg = jnp.asarray(keys), jnp.asarray(grads)
        rows = np.asarray(ht.pull(t, jk, INIT))
        for i, k in enumerate(keys):
            want = replica.get(int(k), np.full(DIM, 0.25, np.float32))
            np.testing.assert_allclose(rows[i], want, rtol=1e-5, atol=1e-6)
        t = ht.apply_gradients(t, opt, INIT, jk, jg)
        # replicate: dedup-sum then single momentumless sgd step
        summed = {}
        for i, k in enumerate(keys):
            summed[int(k)] = summed.get(int(k), np.zeros(DIM, np.float32)) + grads[i]
        for k, g in summed.items():
            cur = replica.get(k, np.full(DIM, 0.25, np.float32))
            replica[k] = cur - lr * g
    assert int(t.insert_failures) == 0


def test_probe_window_overflow_counted():
    """A table with capacity < distinct keys must fail some inserts, not hang
    or corrupt other rows."""
    opt = make_optimizer({"category": "sgd", "learning_rate": 1.0})
    t = ht.create_hash_table(META, opt, capacity=8)
    keys = jnp.arange(100, dtype=jnp.int32) * 7919
    grads = jnp.ones((100, DIM), dtype=jnp.float32)
    t = ht.apply_gradients(t, opt, INIT, keys, grads)
    assert int(t.num_used()) == 8
    assert int(t.insert_failures) == 100 - 8


def test_adam_state_on_hash_rows():
    """Optimizer slots ride along: two updates to one key accumulate state."""
    opt = make_optimizer({"category": "adam", "learning_rate": 0.1})
    t = ht.create_hash_table(META, opt, capacity=32)
    k = jnp.array([77], jnp.int32)
    g = jnp.ones((1, DIM), jnp.float32)
    t = ht.apply_gradients(t, opt, INIT, k, g)
    t = ht.apply_gradients(t, opt, INIT, k, g)
    slot = ht.find_rows(t.keys, k)
    b1 = float(t.slots["beta_1_t"][int(slot[0]), 0])
    np.testing.assert_allclose(b1, 0.9**2, rtol=1e-6)


@pytest.mark.parametrize("data,model", [(1, 8), (2, 4), (8, 1)])
def test_sharded_hash_matches_single(devices8, data, model):
    mesh = create_mesh(data, model, devices8)
    opt = make_optimizer({"category": "adagrad", "learning_rate": 0.1})
    spec = sh.make_hash_sharding_spec(mesh, total_capacity=1024)
    sharded = sh.create_sharded_hash_table(META, opt, mesh=mesh, spec=spec)
    single = ht.create_hash_table(META, opt, capacity=1024)

    rng = np.random.RandomState(2)
    B = 16
    for step in range(3):
        keys = rng.randint(0, 10**8, size=B).astype(np.int32)
        grads = rng.randn(B, DIM).astype(np.float32)
        jk, jg = jnp.asarray(keys), jnp.asarray(grads)

        got = sh.pull_sharded(sharded, jk, INIT, mesh=mesh, spec=spec)
        want = ht.pull(single, jk, INIT)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

        sharded = sh.apply_gradients_sharded(sharded, opt, INIT, jk, jg,
                                             mesh=mesh, spec=spec)
        single = ht.apply_gradients(single, opt, INIT, jk, jg)

    got = sh.pull_sharded(sharded, jnp.asarray(keys), INIT, mesh=mesh, spec=spec)
    want = ht.pull(single, jnp.asarray(keys), INIT)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert int(sharded.insert_failures) == 0


def test_sharded_hash_batch_replicated(devices8):
    mesh = create_mesh(4, 2, devices8)
    opt = make_optimizer({"category": "sgd", "learning_rate": 0.5})
    spec = sh.make_hash_sharding_spec(mesh, total_capacity=256)
    t1 = sh.create_sharded_hash_table(META, opt, mesh=mesh, spec=spec)
    t2 = jax.tree.map(jnp.copy, t1)

    keys = jnp.arange(16, dtype=jnp.int32) * 101
    g = jnp.ones((16, DIM)) * jnp.arange(16)[:, None]

    t1 = sh.apply_gradients_sharded(t1, opt, INIT, keys, g, mesh=mesh,
                                    spec=spec, batch_sharded=True)
    t2 = sh.apply_gradients_sharded(t2, opt, INIT, keys, g, mesh=mesh,
                                    spec=spec, batch_sharded=False)
    r1 = sh.pull_sharded(t1, keys, INIT, mesh=mesh, spec=spec, batch_sharded=True)
    r2 = sh.pull_sharded(t2, keys, INIT, mesh=mesh, spec=spec, batch_sharded=False)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), rtol=1e-6)


@pytest.mark.slow
def test_int64_keys_full_width(devices8):
    """The reference's 2^62 key space: int64 keys end-to-end in a dedicated
    x64 process (the global flag changes dtypes program-wide, so the
    documented deployment shape is a dedicated interpreter)."""
    import os
    import subprocess
    import sys
    worker = os.path.join(os.path.dirname(__file__), "x64_worker.py")
    root = os.path.dirname(os.path.dirname(worker))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": root + os.pathsep + os.environ.get("PYTHONPATH", "")}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, worker], env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "x64 worker: ok" in out.stdout


def test_bucket_layout_and_rounding():
    """Capacity rounds to bucket granularity; layout covers small tables."""
    assert ht.round_capacity(1000) == 1024
    assert ht.round_capacity(8) == 8
    b, nb, chain = ht.table_layout(4096, ht.DEFAULT_MAX_PROBES)
    assert (b, nb, chain) == (128, 32, 2)
    b, nb, chain = ht.table_layout(8, ht.DEFAULT_MAX_PROBES)
    assert (b, nb, chain) == (8, 1, 1)
    with pytest.raises(ValueError):
        ht.table_layout(1000, ht.DEFAULT_MAX_PROBES)


def test_widen_ids_matches_split64():
    """widen_ids (the narrow->wide bridge every default-keyed lookup rides)
    must agree with the host split64 encoding for every sign, and map the
    narrow dtype's sentinel to the EMPTY pair (the invalid contract)."""
    ids32 = np.array([0, 1, -1, 7, -2**31 + 1, 2**31 - 1], np.int32)
    got = np.asarray(ht.widen_ids(jnp.asarray(ids32)))
    np.testing.assert_array_equal(got, ht.split64(ids32.astype(np.int64)))
    # int32 sentinel -> EMPTY pair (both words)
    s = np.asarray(ht.widen_ids(jnp.asarray([np.iinfo(np.int32).min],
                                            np.int32)))
    np.testing.assert_array_equal(s, ht.empty_key(jnp.int32))
    # shape is preserved with a trailing pair axis
    m = np.asarray(ht.widen_ids(jnp.asarray(ids32.reshape(2, 3))))
    assert m.shape == (2, 3, 2)
    # device int64 branch (x64 on): full width + int64 sentinel -> EMPTY
    import jax
    with jax.enable_x64(True):
        ids64 = np.array([2**33 + 7, -5, np.iinfo(np.int64).min], np.int64)
        got64 = np.asarray(ht.widen_ids(jnp.asarray(ids64)))
    np.testing.assert_array_equal(got64[:2], ht.split64(ids64[:2]))
    np.testing.assert_array_equal(got64[2], ht.empty_key(jnp.int32))


def test_pair_mod_matches_int64_mod():
    """pair_mod (the x64-off wide-key shard-owner rule) equals int64
    ``id % g`` for every sign/magnitude — the loader, in-process filter,
    and router all rely on this equivalence."""
    rng = np.random.RandomState(0)
    ids = np.concatenate([
        rng.randint(-2**62, 2**62, 5000).astype(np.int64),
        np.array([0, 1, -1, 2**62 - 1, -2**62, (3 << 60) + (5 << 32)])])
    pairs = jnp.asarray(ht.split64(ids))
    for g in (1, 2, 3, 7, 16, 1000, 32767):
        np.testing.assert_array_equal(
            np.asarray(ht.pair_mod(pairs, g)), ids % g)
    with pytest.raises(ValueError, match="shard count"):
        ht.pair_mod(pairs, 1 << 15)


@pytest.mark.slow
def test_pallas_probe_gather_parity():
    """Fused Pallas probe+gather (interpret mode) matches find_rows+take.

    Covers hits, misses, and the EMPTY sentinel. The kernel is the native
    form of the reference's probe-and-copy pull loop
    (EmbeddingPullOperator.cpp:149-252); the bucket-row XLA probe is the
    default pull path and the kernel is the alternative bench.py's
    ``hash_probe_dim128`` config times against it.
    """
    from openembedding_tpu.ops import pallas_hash as ph
    cap, dim = 2048, 128
    rng = np.random.RandomState(3)
    empty = ht.empty_key(jnp.int32)
    tk = jnp.full((cap,), empty, jnp.int32)
    nk = jnp.asarray(rng.randint(1, 1 << 30, size=700).astype(np.int32))
    tk, slot, ins, failed = ht.find_or_insert(tk, nk, nk != empty)
    assert int(failed.sum()) == 0
    weights = jnp.asarray(rng.randn(cap, dim).astype(np.float32))
    q = jnp.concatenate([
        nk[:300],
        jnp.asarray(rng.randint(1 << 30, 1 << 31, size=60, dtype=np.int32)),
        jnp.asarray([empty], jnp.int32)])
    bsz, nb, chain = ht.table_layout(cap, ht.DEFAULT_MAX_PROBES)
    starts = ht.probe_starts(q, cap, ht.DEFAULT_MAX_PROBES)
    rows, hit = ph.probe_gather(tk, weights, starts, q, chain=chain,
                                bucket=bsz, empty=empty, interpret=True)
    slots = ht.find_rows(tk, q)
    want_hit = np.asarray(slots) >= 0
    np.testing.assert_array_equal(np.asarray(hit), want_hit)
    want = np.where(want_hit[:, None],
                    np.asarray(weights)[np.maximum(np.asarray(slots), 0)],
                    0.0)
    np.testing.assert_array_equal(np.asarray(rows), want)


def test_wide_keys_full_width_without_x64():
    """64-bit key space in a DEFAULT (x64-off) process: keys are [n, 2]
    int32 (lo, hi) pairs, so ids that differ only above bit 31 must map to
    distinct rows — the aliasing an int32 table would silently commit.
    Covers the reference's 2^62 hashed key space
    (criteo_deepctr.py to_hash_bucket_fast(2**62)) without the global flag.
    """
    assert not jax.config.jax_enable_x64
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=2**63)
    opt = make_optimizer({"category": "sgd", "learning_rate": 1.0})
    init = {"category": "constant", "value": 0.0}
    t = ht.create_hash_table(meta, opt, capacity=1024, key_width=64)
    assert t.wide and t.keys.shape == (1024, 2)

    # keys congruent mod 2^32: identical lo words, distinct hi words
    base = np.asarray([12345, 12345 + (1 << 32), 12345 + (5 << 40),
                       -(7 << 35) + 12345], np.int64)
    pairs = jnp.asarray(ht.split64(base))
    assert np.asarray(pairs[:, 0]).tolist() == [np.int32(12345)] * 4
    g = jnp.asarray(np.arange(1, 5, dtype=np.float32))[:, None] * \
        jnp.ones((4, DIM), jnp.float32)
    t = ht.apply_gradients(t, opt, init, pairs, g)
    assert int(t.insert_failures) == 0
    assert int(t.num_used()) == 4  # four distinct rows, no aliasing
    rows = np.asarray(ht.pull(t, pairs, None))
    np.testing.assert_allclose(rows[:, 0], [-1.0, -2.0, -3.0, -4.0],
                               rtol=1e-6)

    # round-trip through the host helpers
    np.testing.assert_array_equal(ht.join64(ht.split64(base)), base)

    # duplicate pairs combine exactly once per key (pair dedup)
    dup = jnp.asarray(ht.split64(np.asarray(
        [99, 99 + (1 << 32), 99, 99 + (1 << 32)], np.int64)))
    t = ht.apply_gradients(t, opt, init, dup,
                           jnp.ones((4, DIM), jnp.float32))
    rows = np.asarray(ht.pull(t, dup[:2], None))
    # sgd with count semantics: grads summed per unique key
    np.testing.assert_allclose(rows[:, 0], -2.0, rtol=1e-6)

    # pull of an absent wide key returns the deterministic init row (zeros
    # under constant-0) and EMPTY-hi pairs return zeros
    probe = jnp.asarray(ht.split64(np.asarray([424242 + (9 << 33)],
                                              np.int64)))
    np.testing.assert_allclose(np.asarray(ht.pull(t, probe, init)), 0.0)

    # wide tables refuse narrow queries ([B, F] narrow-table indices are
    # legitimately any-shape, so only the wide side can police shapes)
    with pytest.raises(ValueError, match="key-shape mismatch"):
        ht.pull(t, jnp.asarray([1, 2], jnp.int32), None)


def test_wide_keys_insert_rows_and_find():
    """Load-path delivery + find on a wide-key table."""
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=2**63)
    opt = make_optimizer({"category": "default"})
    t = ht.create_hash_table(meta, opt, capacity=256, key_width=64)
    k64 = np.asarray([7, 7 + (1 << 32), (3 << 45) + 1], np.int64)
    pairs = jnp.asarray(ht.split64(k64))
    w = jnp.asarray(np.eye(3, DIM, dtype=np.float32) * 5.0)
    t = ht.insert_rows(t, pairs, w)
    assert int(t.insert_failures) == 0
    slots = ht.find_rows(t.keys, pairs)
    assert (np.asarray(slots) >= 0).all()
    assert len(set(np.asarray(slots).tolist())) == 3
    got = np.asarray(ht.pull(t, pairs, None))
    np.testing.assert_allclose(got, np.asarray(w), rtol=1e-6)


# --- two-phase find_or_insert against the full-width level loop -------------

TP_N = 2048                     # keys a call; insert_width(TP_N) == 1024
TP_CAPACITY = 1 << 14           # 128 buckets, a chain of two
TP_BUCKET = 40                  # the start bucket the contended cases fill
TP_TRIP = 128                   # table.INSERT_CHUNK, set small for the trips


def _level_loop_oracle(table_keys, new_keys, valid, max_probes):
    """The insert loop over ALL keys of the call, as find_or_insert ran it
    before it found present keys first: the oracle of the cases below."""
    capacity, n = table_keys.shape[0], new_keys.shape[0]
    empty = ht.empty_key(table_keys.dtype)
    wide = ht.is_wide(table_keys)
    bsz, nb, chain = ht.table_layout(capacity, max_probes)
    b0 = ht.probe_starts(new_keys, capacity, max_probes) // bsz
    ids = jnp.arange(n, dtype=jnp.int32)
    slot = jnp.full((n,), -1, jnp.int32)
    done = ~valid
    inserted = jnp.zeros((n,), bool)
    for j in range(chain):
        bj = b0 + j
        start = bj * bsz
        if wide:
            rows = jnp.take(table_keys.reshape(nb, bsz, 2), bj, axis=0)
            match = ((rows[..., 0] == new_keys[:, None, 0])
                     & (rows[..., 1] == new_keys[:, None, 1]))
            emptym = rows[..., 1] == empty
        else:
            rows = jnp.take(table_keys.reshape(nb, bsz), bj, axis=0)
            match = rows == new_keys[:, None]
            emptym = rows == empty
        active = valid & ~done
        hitm = active & jnp.any(match, axis=1)
        slot = jnp.where(hitm, start + jnp.argmax(match, axis=1), slot)
        done = done | hitm
        active = active & ~hitm
        bid = jnp.where(active, bj, nb)
        order = jnp.argsort(bid, stable=True)
        sorted_bid = bid[order]
        seg = jnp.concatenate([
            jnp.ones((1,), bool), sorted_bid[1:] != sorted_bid[:-1]])
        group_start = jax.lax.cummax(jnp.where(seg, ids, 0))
        rank = jnp.zeros((n,), jnp.int32).at[order].set(ids - group_start)
        cum = jnp.cumsum(emptym, axis=1).astype(jnp.int32)
        place = active & (rank < cum[:, -1])
        tgt = jnp.argmax((cum == rank[:, None] + 1) & emptym, axis=1)
        pslot = (start + tgt).astype(jnp.int32)
        table_keys = table_keys.at[
            jnp.where(place, pslot, capacity)].set(new_keys, mode="drop")
        slot = jnp.where(place, pslot, slot).astype(jnp.int32)
        done = done | place
        inserted = inserted | place
    return table_keys, slot, inserted, valid & ~done


def _assert_same_call(got, want):
    """Two ``find_or_insert`` results, equal bit for bit."""
    for name, g, w in zip(("keys", "slot", "inserted", "failed"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)


@functools.lru_cache(maxsize=None)
def _tp_table(wide):
    """(table keys holding ``present``, present [TP_N], fresh [TP_N],
    contending [400], neighbours [40]): distinct keys; the contending all
    start at bucket TP_BUCKET, whose chain holds 256, the neighbours (in
    no table and no other list) at the bucket after it, where that chain
    ends."""
    rng = np.random.RandomState(7)
    if wide:
        pool = np.unique(rng.randint(1, 2**62, size=80000, dtype=np.int64))
    else:
        pool = np.unique(rng.randint(1, 2**31 - 1, size=80000)
                         .astype(np.int32))
    rng.shuffle(pool)

    def as_keys(k):
        return jnp.asarray(ht.split64(k) if wide else k)

    start = np.asarray(ht.probe_starts(as_keys(pool), TP_CAPACITY,
                                       ht.DEFAULT_MAX_PROBES)) // ht.BUCKET
    crowd = pool[start == TP_BUCKET][:400]
    assert len(crowd) == 400
    rest = pool[start != TP_BUCKET]
    beside = rest[start[start != TP_BUCKET] == TP_BUCKET + 1]
    beside = np.setdiff1d(beside, rest[:2 * TP_N])[:40]
    assert len(beside) == 40
    present = as_keys(rest[:TP_N])
    shape = (TP_CAPACITY, 2) if wide else (TP_CAPACITY,)
    table = jnp.full(shape, ht.empty_key(jnp.int32), jnp.int32)
    table, slot, _, failed = _level_loop_oracle(
        table, present, jnp.ones((TP_N,), bool), ht.DEFAULT_MAX_PROBES)
    assert not bool(failed.any()) and bool((slot >= 0).all())
    return (table, present, as_keys(rest[TP_N:2 * TP_N]), as_keys(crowd),
            as_keys(beside))


def _tp_case(case, wide):
    """(table keys holding the present keys, the call's keys, valid,
    how many of them miss)."""
    table, present, fresh, crowd, beside = _tp_table(wide)
    empty = ht.empty_key(jnp.int32)
    m = ht.insert_width(TP_N)
    assert m == 1024
    misses = {"none_missing": 0, "five_percent": 102, "exactly_m": m,
              "m_plus_1": m + 1, "all_missing": TP_N,
              "bucket_overflow": 200, "window_full": 400,
              "one_trip": TP_TRIP, "one_trip_and_one": TP_TRIP + 1,
              "overflow_across_trips": 2 * TP_TRIP + 20,
              "window_full_across_trips": 4 * TP_TRIP + 20}[case]
    new = fresh[:misses]
    if case == "bucket_overflow":   # 200 > the 128 slots of one bucket
        new = crowd[:200]
    if case == "window_full":       # 400 > the 256 slots of the chain
        new = crowd
    # The misses keep their order in the compact buffer, so at TP_TRIP
    # keys a trip: the first trip fills the bucket, the second holds the
    # contenders that overflow, and a later one the keys that START where
    # those overflow to. A level at a time the late keys take that
    # bucket's first free slots and the overflowing ones the next; a trip
    # at a time with every level inside it would hand them out the other
    # way round.
    if case == "overflow_across_trips":
        new = jnp.concatenate([crowd[:200], fresh[:2 * TP_TRIP - 200],
                               beside[:20]])
    if case == "window_full_across_trips":
        new = jnp.concatenate([crowd, fresh[:4 * TP_TRIP - 400],
                               beside[:20]])
    # the misses spread among the hits, one invalid key in the call
    keys = np.asarray(present).copy()
    at = np.random.RandomState(3).permutation(TP_N)[:misses]
    keys[np.sort(at)] = np.asarray(new)
    valid = np.ones((TP_N,), bool)
    if misses < TP_N:
        gap = np.setdiff1d(np.arange(TP_N), at)[5]
        keys[gap] = empty
        valid[gap] = False
    return table, jnp.asarray(keys), jnp.asarray(valid), misses


TP_CASES = ["none_missing", "five_percent", "exactly_m", "m_plus_1",
            "all_missing", "bucket_overflow", "window_full"]


@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "wide"])
@pytest.mark.parametrize("case", TP_CASES)
def test_two_phase_find_or_insert_equals_level_loop(case, wide):
    """Find first, insert the misses from a compacted buffer (or, past its
    width, from the whole call): keys, slots, inserted and failed equal the
    full-width loop's bit for bit whichever loop placed them."""
    table, keys, valid, misses = _tp_case(case, wide)
    want = _level_loop_oracle(table, keys, valid, ht.DEFAULT_MAX_PROBES)
    got = jax.jit(ht.find_or_insert)(table, keys, valid)
    _assert_same_call(got, want)
    _, slot, inserted, failed = map(np.asarray, got)
    assert int((inserted | failed).sum()) == misses
    if case == "bucket_overflow":
        level = (slot[inserted] // ht.BUCKET) - TP_BUCKET
        assert set(level.tolist()) == {0, 1} and not failed.any()
    if case == "window_full":
        assert failed.sum() > 100 and (slot[failed] == -1).all()
        state = ht.HashTableState(
            keys=table, weights=jnp.zeros((TP_CAPACITY, DIM)), slots={},
            init_rng=jax.random.PRNGKey(0),
            insert_failures=jnp.asarray(3, jnp.int32))
        state = jax.jit(ht.insert_rows)(state, keys,
                                        jnp.ones((TP_N, DIM)))
        assert int(state.insert_failures) == 3 + int(failed.sum())
    else:
        assert not failed.any()


# case -> (trips a level, levels) of the compact loop at TP_TRIP keys a trip
TRIP_CASES = {"none_missing": (0, 0), "five_percent": (1, 1),
              "one_trip": (1, 1), "one_trip_and_one": (2, 1),
              "exactly_m": (8, 1), "m_plus_1": (0, 0),
              "bucket_overflow": (2, 2), "window_full": (4, 2),
              "overflow_across_trips": (3, 2),
              "window_full_across_trips": (5, 2)}


@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "wide"])
@pytest.mark.parametrize("case", TRIP_CASES)
def test_compact_insert_walks_its_misses_in_trips(monkeypatch, case, wide):
    """The compact loop places the misses a trip of ``table.INSERT_CHUNK``
    keys at a time, level by level: no trip, one, one exactly full, several,
    all the buffer holds, and contenders for one bucket on both sides of a
    trip's end. Keys, slots, inserted and failed are the full-width loop's
    bit for bit, and the loop walks the trips that hold a miss, once a
    level."""
    from test_table import recorded
    monkeypatch.setattr(ht.table_lib, "INSERT_CHUNK", TP_TRIP)
    table, keys, valid, misses = _tp_case(case, wide)
    want = _level_loop_oracle(table, keys, valid, ht.DEFAULT_MAX_PROBES)
    got, stats = recorded(lambda: jax.jit(
        lambda t, k, v: ht.find_or_insert(t, k, v, record_stats=True))(
            table, keys, valid))
    _assert_same_call(got, want)
    trips, levels = TRIP_CASES[case]
    assert trips == (-(-misses // TP_TRIP) if misses <= 1024 else 0)
    assert stats.get("hash_insert_keys_walked", 0) == levels * trips * TP_TRIP
    assert stats["hash_insert_missed"] == misses
    _, slot, inserted, failed = map(np.asarray, got)
    assert int((inserted | failed).sum()) == misses
    if "across_trips" in case:
        # the keys in their compact order: the crowd, filler, the late keys
        new = slot[np.flatnonzero(inserted | failed)]
        crowd = new[:200 if case == "overflow_across_trips" else 400]
        late = new[-20:]
        beyond = crowd[crowd >= (TP_BUCKET + 1) * ht.BUCKET]
        assert (crowd[:128] // ht.BUCKET == TP_BUCKET).all()
        assert (late // ht.BUCKET == TP_BUCKET + 1).all()
        assert len(beyond) >= 72 and late.max() < beyond.min()
        assert (crowd[128:] < 0).sum() == failed.sum() == \
            (0 if case == "overflow_across_trips" else 400 - 128 - len(beyond))


@pytest.mark.parametrize("case,compact,full,walked", [
    ("five_percent", 1, 0, 1024), ("exactly_m", 1, 0, 1024),
    ("m_plus_1", 0, 1, 0), ("all_missing", 0, 1, 0),
    ("bucket_overflow", 1, 0, 2048)])
def test_find_or_insert_counts_its_branch(case, compact, full, walked):
    from openembedding_tpu.utils import observability
    table, keys, valid, misses = _tp_case(case, True)
    fn = jax.jit(lambda t, k, v: ht.find_or_insert(t, k, v,
                                                   record_stats=True))
    observability.GLOBAL.reset()
    observability.set_evaluate_performance(True)
    try:
        jax.block_until_ready(fn(table, keys, valid))
        jax.effects_barrier()
    finally:
        observability.set_evaluate_performance(False)
    got = observability.GLOBAL.snapshot()
    observability.GLOBAL.reset()
    # the find of a call of one chunk or less walks the call, and a buffer
    # of one trip or less is walked whole, once a level
    assert {k: int(got.get(k, {}).get("count", 0)) for k in (
        "hash_insert_compact", "hash_insert_full", "hash_insert_missed",
        "hash_insert_keys_walked",
        "hash_find_slots_live", "hash_find_slots_walked")
    } == {"hash_insert_compact": compact, "hash_insert_full": full,
          "hash_insert_missed": misses,
          "hash_insert_keys_walked": walked,
          "hash_find_slots_live": int(valid.sum()),
          "hash_find_slots_walked": TP_N}


def test_find_or_insert_default_program_has_no_host_callback(monkeypatch):
    from openembedding_tpu.analysis import contracts
    table, keys, valid, _ = _tp_case("five_percent", True)

    def compiled(keys, valid, **kw):
        return jax.jit(lambda t, k, v: ht.find_or_insert(t, k, v, **kw)
                       ).lower(table, keys, valid).compile().as_text()

    # the two insert loops (the compact one holds its loop over the trips
    # of a level), chosen by what they are given to do and by no
    # conditional (which would copy the key array); a find of one chunk
    # or less is one pass and no loop
    default = compiled(keys, valid)
    contracts.check_no_host_transfers(default)
    assert default.count(" while(") == 3 and " conditional(" not in default
    recording = compiled(keys, valid, record_stats=True)
    assert contracts.host_transfer_ops(recording) == ["host-callback"] * 6
    # a call no wider than the buffer is the loop alone
    small = compiled(keys[:1024], valid[:1024])
    assert small.count(" while(") == 1
    # wider than a chunk, the find is one more loop, and records as little
    monkeypatch.setattr(ht.table_lib, "FIND_CHUNK", FIND_CHUNK)
    chunked = compiled(keys, valid)
    contracts.check_no_host_transfers(chunked)
    assert chunked.count(" while(") == 4 and " conditional(" not in chunked


# --- the chunked find (find_or_insert's first phase) -------------------------

FIND_CHUNK = 512                    # table.FIND_CHUNK, set small for these
FIND_N = 3 * FIND_CHUNK + 100       # three chunks and a remainder
FIND_MASKS = {"none_valid": 0, "one_valid": 1, "one_chunk": FIND_CHUNK,
              "one_chunk_and_one": FIND_CHUNK + 1, "all_valid": FIND_N,
              "holes_and_valid_last_slot": None}


def _find_case(mask, wide):
    """(table keys, the call's keys, valid): a twentieth of the keys are
    not in the table, and the slots the mask leaves out hold real keys,
    absent ones among them, which a find must neither report nor place."""
    table, present, fresh, _crowd, _beside = _tp_table(wide)
    keys = np.asarray(present)[:FIND_N].copy()
    at = np.random.RandomState(5).permutation(FIND_N)[:FIND_N // 20]
    keys[at] = np.asarray(fresh)[:len(at)]
    live = FIND_MASKS[mask]
    if live is None:
        valid = np.random.RandomState(9).rand(FIND_N) < 0.5
        valid[-1] = True
    else:
        valid = np.arange(FIND_N) < live
    return table, jnp.asarray(keys), jnp.asarray(valid)


@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "wide"])
@pytest.mark.parametrize("mask", FIND_MASKS)
def test_chunked_find_equals_level_loop(monkeypatch, mask, wide):
    """A call wider than a chunk finds in trips over the occupied prefix:
    the key array, slots, inserted and failed are the full-width loop's
    bit for bit, whatever the mask."""
    monkeypatch.setattr(ht.table_lib, "FIND_CHUNK", FIND_CHUNK)
    table, keys, valid = _find_case(mask, wide)
    assert ht.insert_width(FIND_N) < FIND_N     # the call has a find phase
    want = _level_loop_oracle(table, keys, valid, ht.DEFAULT_MAX_PROBES)
    got = jax.jit(ht.find_or_insert)(table, keys, valid)
    _assert_same_call(got, want)
    _, slot, inserted, failed = map(np.asarray, got)
    valid = np.asarray(valid)
    assert not failed.any() and (slot[~valid] == -1).all()
    assert (slot[valid] >= 0).all() and not inserted[~valid].any()
    if mask == "all_valid":
        assert inserted.sum() == FIND_N // 20


@pytest.mark.parametrize("mask", FIND_MASKS)
def test_chunked_find_counts_the_keys_it_walks(monkeypatch, mask):
    from test_table import recorded
    monkeypatch.setattr(ht.table_lib, "FIND_CHUNK", FIND_CHUNK)
    table, keys, valid = _find_case(mask, True)
    _, stats = recorded(lambda: jax.jit(
        lambda t, k, v: ht.find_or_insert(t, k, v, record_stats=True))(
            table, keys, valid))
    valid = np.asarray(valid)
    bound = int(np.flatnonzero(valid).max()) + 1 if valid.any() else 0
    assert {k: stats.get(k, 0) for k in (
        "hash_find_slots_live", "hash_find_slots_walked")} == {
            "hash_find_slots_live": int(valid.sum()),
            "hash_find_slots_walked": -(-bound // FIND_CHUNK) * FIND_CHUNK}


@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "wide"])
@pytest.mark.parametrize("width", ["small_call", "chunked_call"])
@pytest.mark.parametrize("mask", ["one_valid", "all_valid",
                                  "holes_and_valid_last_slot"])
def test_a_find_handed_over_is_not_made_again(monkeypatch, mask, width,
                                              wide):
    """``found`` is the find phase's answer (what a step's pull found for
    the keys its push brings): with it the key array, slots, inserted and
    failed are the call's without, bit for bit; a call wider than a chunk
    holds no loop for the find and counts no key walked, and a small call,
    all insert loop, takes it as the keys the loop may leave alone."""
    from test_table import recorded
    monkeypatch.setattr(ht.table_lib, "FIND_CHUNK", FIND_CHUNK)
    table, keys, valid = _find_case(mask, wide)
    if width == "small_call":
        keys, valid = keys[:1024], valid[:1024]
        assert ht.insert_width(1024) == 1024
    found, _ = ht._find_levels(table, keys, valid, ht.DEFAULT_MAX_PROBES)
    assert (np.asarray(found)[~np.asarray(valid)] == -1).all()
    want = jax.jit(ht.find_or_insert)(table, keys, valid)
    handed = jax.jit(lambda t, k, v, f: ht.find_or_insert(
        t, k, v, record_stats=True, found=f))
    got, stats = recorded(lambda: handed(table, keys, valid, found))
    _assert_same_call(got, want)
    assert stats["hash_insert_missed"] == int(np.asarray(want[2]).sum())
    loops = lambda fn, *args: fn.lower(table, keys, valid, *args
                                       ).compile().as_text().count(" while(")
    if width == "chunked_call":
        assert stats["hash_find_slots_walked"] == 0
        assert stats["hash_find_slots_live"] == int(np.asarray(valid).sum())
        assert loops(handed, found) == 3 and \
            loops(jax.jit(ht.find_or_insert)) == 4
    else:
        assert loops(handed, found) == 1


# --- the chunked sparse apply (table.apply_rows) on the hash path -----------

CHUNK = 8       # table.APPLY_CHUNK, set small for these tests
ADAGRAD = {"category": "adagrad", "learning_rate": 0.5}
UNIFORM = {"category": "uniform", "minval": -1.0, "maxval": 1.0}


def _apply_case(case, wide):
    """(table capacity, keys in it, the push's keys as int64, empties mixed
    in): 6 keys are in the table before the push."""
    rng = np.random.default_rng(29)
    pool = rng.permutation(np.arange(1, 5000, dtype=np.int64))[:64]
    if wide:        # distinct high words and equal low words among them
        pool = pool + (pool % 5 << 40) - (pool % 3 << 35)
    present, fresh = pool[:6], pool[6:]
    mixed = np.concatenate([present[:4], fresh])
    capacity, empties = 1024, 0
    if case == "no_live_key":
        push, empties = mixed[:0], 20
    elif case == "one_live_key":
        push = np.full(3 * CHUNK, fresh[0])
    elif case == "one_chunk":
        push = np.resize(mixed[:CHUNK], 3 * CHUNK)
    elif case == "one_chunk_and_one":
        push, empties = np.resize(mixed[:CHUNK + 1], 3 * CHUNK - 2), 2
    elif case == "ragged_capacity":     # 21 slots in chunks of 8
        push, empties = np.resize(mixed[:18], 20), 1
    elif case == "every_slot_live":
        push = mixed[:3 * CHUNK]
    elif case == "fresh_and_failed":    # 16 slots for 6 + 20 keys
        push, capacity = mixed[:3 * CHUNK], 16
    elif case == "fits_one_chunk":      # no loop: the body once
        push = np.resize(mixed[:3], CHUNK)
    else:
        raise ValueError(case)
    return capacity, present, push, empties


HASH_APPLY_CASES = ["no_live_key", "one_live_key", "one_chunk",
                    "one_chunk_and_one", "ragged_capacity",
                    "every_slot_live", "fresh_and_failed", "fits_one_chunk"]


@pytest.mark.parametrize("in_counts", [False, True], ids=["", "in_counts"])
@pytest.mark.parametrize("wide", [False, True], ids=["narrow", "wide"])
@pytest.mark.parametrize("case", HASH_APPLY_CASES)
def test_chunked_apply_is_the_plain_apply_bit_for_bit(monkeypatch, case,
                                                      wide, in_counts):
    """Against a NumPy apply that takes each key's slot from the new key
    array (placing keys is the probe's, tested above): a key that is in the
    table afterwards holds Adagrad's update of its row, or of its initial
    row if the push inserted it; no other row moved; a key the window had no
    room for is counted."""
    from test_table import dyadic, numpy_adagrad, recorded
    monkeypatch.setattr(ht.table_lib, "APPLY_CHUNK", CHUNK)
    rng = np.random.default_rng(7)
    capacity, present, push, empties = _apply_case(case, wide)
    opt = make_optimizer(ADAGRAD)

    def as_keys(k64):
        return jnp.asarray(ht.split64(k64) if wide
                           else k64.astype(np.int32))

    empty = ht.empty_key(jnp.int32)
    keys = np.asarray(as_keys(push))
    keys = np.concatenate([keys, np.full((empties,) + keys.shape[1:],
                                         empty, np.int32)])
    keys = keys[rng.permutation(len(keys))]
    n = len(keys)
    grads = dyadic(rng, (n, DIM))
    counts = rng.integers(1, 4, size=n) if in_counts else None
    before = ht.create_hash_table(META, opt, capacity=capacity,
                                  key_width=64 if wide else 32)
    before = ht.insert_rows(before, as_keys(present),
                            dyadic(rng, (6, DIM)),
                            {"accum": 1 + dyadic(rng, (6, DIM)) ** 2})
    new, stats = recorded(lambda: jax.jit(
        lambda t, k, g, c: ht.apply_gradients(
            t, opt, UNIFORM, k, g, in_counts=c, record_stats=True))(
                before, jnp.asarray(keys), grads, counts))

    # distinct keys in the unique buffer's order: by (high word,) low word
    distinct = np.unique(keys.reshape(n, -1)[:, ::-1], axis=0)[:, ::-1]
    query = jnp.asarray(distinct.reshape((-1,) + keys.shape[1:]))
    valid = distinct[:, -1] != empty
    was = np.asarray(ht.find_rows(before.keys, query))
    now = np.asarray(ht.find_rows(new.keys, query))
    initial = np.asarray(ht.pull(before, query, UNIFORM))
    want_w = np.asarray(before.weights).copy()
    want_a = np.asarray(before.slots["accum"]).copy()
    for i in np.flatnonzero(valid & (now >= 0)):
        g = grads[(keys.reshape(n, -1) == distinct[i]).all(axis=1)].sum(
            axis=0, dtype=np.float32)
        assert was[i] in (-1, now[i])
        w = want_w[now[i]] if was[i] >= 0 else initial[i]
        want_w[now[i]], want_a[now[i]] = numpy_adagrad(w, want_a[now[i]], g)
    np.testing.assert_array_equal(np.asarray(new.weights), want_w)
    np.testing.assert_array_equal(np.asarray(new.slots["accum"]), want_a)
    failed = int((valid & (now < 0)).sum())
    assert int(new.insert_failures) == failed
    assert failed == (10 if case == "fresh_and_failed" else 0)
    live = valid & (now >= 0)
    bound = int(np.flatnonzero(live).max()) + 1 if live.any() else 0
    walked = n if n <= CHUNK else -(-bound // CHUNK) * CHUNK
    assert {k: stats.get(k, 0) for k in (
        "apply_slots_live", "apply_slots_walked")} == {
            "apply_slots_live": int(live.sum()),
            "apply_slots_walked": walked}

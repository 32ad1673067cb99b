"""Owner-routed all-to-all data plane: parity + routing invariants.

The a2a plane must be numerically indistinguishable from the psum plane (and
from the single-device core) — same contract the reference enforces between
its one-node and N-node paths (c_api_test.h matrix). Routing internals
(bucketing, grid transpose, overflow accounting) are checked separately.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu import (EmbeddingCollection, EmbeddingSpec,
                               EmbeddingVariableMeta, apply_gradients,
                               create_table, make_optimizer, pull)
from openembedding_tpu import hash_table as hash_lib
from openembedding_tpu.parallel import alltoall as a2a
from openembedding_tpu.parallel import sharded_hash as sh
from openembedding_tpu.parallel import sharded_table as st
from openembedding_tpu.parallel.mesh import create_mesh

VOCAB, DIM = 64, 4


# --- routing primitives -----------------------------------------------------

def test_bucketize_assigns_dense_slots():
    owner = jnp.asarray([2, 0, 2, 5, 0, 2], jnp.int32)  # 5 >= num_shards: drop
    dest, ok = a2a.bucketize(owner, num_shards=4, capacity=2)
    dest, ok = np.asarray(dest), np.asarray(ok)
    assert not ok[3] and dest[3] == 4 * 2
    # owner 0 entries fill slots 0..1 of bucket 0; owner 2 fills bucket 2,
    # third owner-2 entry overflows capacity 2
    assert sorted(dest[[1, 4]].tolist()) == [0, 1]
    in2 = dest[[0, 2, 5]]
    assert sorted(in2.tolist())[:2] == [2 * 2, 2 * 2 + 1]
    assert ok.sum() == 4  # one dropped by owner, one by capacity


def test_bucket_capacity_floors_and_exact():
    # small slices are exact (capacity == slice size)
    assert a2a.bucket_capacity(16, 8) == 16
    # large slices get slack * mean rounded to 8
    c = a2a.bucket_capacity(4096, 8, slack=2.0)
    assert c >= 2 * (4096 // 8) and c % 8 == 0
    # explicit override wins
    assert a2a.bucket_capacity(4096, 8, capacity=128) == 128


def test_residue_accumulators_gated(devices8):
    """Structured-skew overflow is exact AND observable via gated counters."""
    from openembedding_tpu.utils import observability as obs
    mesh = create_mesh(1, 8, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=8 * 512)
    opt = make_optimizer({"category": "sgd", "learning_rate": 0.1})
    # capacity 4 per destination + 64 keys all owned by shard 0 => the
    # residue loop must run extra rounds (and the counters must see them)
    spec = st.make_sharding_spec(meta, mesh, plane="a2a", a2a_capacity=4)
    state = st.create_sharded_table(
        meta, opt, {"category": "constant", "value": 0.5}, mesh=mesh,
        spec=spec)
    idx = jnp.asarray(np.arange(0, 8 * 64, 8, dtype=np.int32))  # all ≡ 0 mod 8
    obs.GLOBAL.reset()
    obs.set_evaluate_performance(True)
    try:
        rows = st.pull_sharded(state, idx, mesh=mesh, spec=spec,
                               batch_sharded=False)
        # exactness despite 16x overflow of the per-round capacity
        np.testing.assert_allclose(np.asarray(rows), 0.5, rtol=1e-6)
        jax.effects_barrier()
        snap = obs.GLOBAL.snapshot()
        assert snap.get("a2a_extra_entries_pull", {}).get("count", 0) > 0
    finally:
        obs.set_evaluate_performance(False)
        obs.GLOBAL.reset()


def test_routing_overflow_counts(devices8):
    # 1 hot owner: every key lands on shard 0 => overflow for small capacity
    idx = np.arange(0, 8 * 64, 8, dtype=np.int32)  # all ≡ 0 mod 8
    n = a2a.routing_overflow(idx, num_shards=8, slice_parts=1,
                             owner_of=lambda u: u % 8, capacity=16)
    assert n == 64 - 16
    # uniform keys with auto capacity: no overflow
    idx = np.arange(512, dtype=np.int32)
    assert a2a.routing_overflow(idx, 8, 1, lambda u: u % 8) == 0


@pytest.mark.parametrize("capacity,spilled", [(0, 0), (1, 1)],
                         ids=["fits", "spills"])
def test_plan_exchange_is_what_a_hand_count_says(devices8, capacity, spilled):
    """Eight ids over data 2 x model 2, two a device, owner ``key % 4``:
    the routed plan holds each device's distinct keys and how often it
    asked for each, their owners and bucket slots, the keys each owner
    received deduplicated with their counts summed over the senders, and
    how many keys round 1 left; the counters say the same."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from openembedding_tpu.ops import dedup
    from openembedding_tpu.utils import observability as obs
    mesh = create_mesh(2, 2, devices8[:4])
    axes = ("data", "model")
    fill = int(dedup.FILL)
    # device 0: {0}; 1: {4, 8}, both owner 0's; 2: {4} and a key no shard
    # owns; 3: {9}
    ids = jnp.asarray([0, 0, 8, 4, 4, -1, 9, 9], jnp.int32)

    def owner_fn(keys):
        return jnp.where(keys >= 0, keys % 4, 4).astype(jnp.int32)

    def plan(idx):
        return a2a.plan_exchange(
            idx, owner_fn, sentinel=fill, num_shards=4, grid_axes=axes,
            grid_sizes=(2, 2), split_axes=("model",), split_sizes=(2,),
            capacity=capacity, record_stats=True)

    own = P(axes)
    mine = dedup.Plan(uniq=own, inverse=own, valid=own, counts=own)
    obs.GLOBAL.reset()
    obs.set_evaluate_performance(True)
    try:
        got = jax.jit(shard_map(
            plan, mesh=mesh, in_specs=(P("data"),),
            out_specs=a2a.RoutedPlan(sender=mine, owners=own, dest=own,
                                     ok=own, owner=mine, spilled=P()),
            check_vma=False))(ids)
        jax.effects_barrier()
        counted = {k: int(v["count"])
                   for k, v in obs.GLOBAL.snapshot().items()}
    finally:
        obs.set_evaluate_performance(False)
        obs.GLOBAL.reset()
    cap = capacity or 2                 # a bucket holds a slice of two
    assert np.asarray(got.sender.uniq).reshape(4, 2).tolist() == [
        [0, fill], [4, 8], [-1, 4], [9, fill]]
    assert np.asarray(got.sender.inverse).reshape(4, 2).tolist() == [
        [0, 0], [1, 0], [1, 0], [0, 0]]
    assert np.asarray(got.sender.counts).reshape(4, 2).tolist() == [
        [2, 0], [1, 1], [1, 1], [2, 0]]
    assert np.asarray(got.owners).reshape(4, 2).tolist() == [
        [0, 4], [0, 0], [4, 0], [1, 4]]
    ok = np.asarray(got.ok).reshape(4, 2)
    assert ok.tolist() == [[True, False], [True, not spilled], [False, True],
                           [True, False]]
    # bucket ``owner`` of a device's send buffer, filled from its start
    dest = np.asarray(got.dest).reshape(4, 2)
    assert dest[ok].tolist() == [0, 0] + [1] * (not spilled) + [0, cap]
    assert (dest[~ok] == 4 * cap).all()
    assert int(got.spilled) == spilled
    # owner 0 holds 0 and 4 (from two senders) and 8 where it fitted,
    # owner 1 holds 9, the others nothing; padding is no key
    held = [[0, 4] + [8] * (not spilled), [9], [], []]
    uniq = np.asarray(got.owner.uniq).reshape(4, 4 * cap)
    valid = np.asarray(got.owner.valid).reshape(4, 4 * cap)
    assert [uniq[i][valid[i]].tolist() for i in range(4)] == held
    # a key's positions over all its senders: 4 was asked for by two
    counts = np.asarray(got.owner.counts).reshape(4, 4 * cap)
    assert [counts[i][valid[i]].tolist() for i in range(4)] == [
        [2, 2] + [1] * (not spilled), [2], [], []]
    received = [[0, 4] + [8] * (not spilled) + [4], [9], [], []]
    back = np.take_along_axis(
        uniq, np.asarray(got.owner.inverse).reshape(4, 4 * cap), axis=1)
    assert [sorted(k for k in row if k != fill) for row in back.tolist()] \
        == [sorted(r) for r in received]
    assert counted == {"routed_plan_keys_sent": int(ok.sum()),
                       "routed_plan_owner_keys_live": int(valid.sum()),
                       "routed_plan_owner_slots": 4 * 4 * cap}
    assert (counted["routed_plan_keys_sent"],
            counted["routed_plan_owner_keys_live"]) == (5 - spilled,
                                                        4 - spilled)


# --- array-table parity ------------------------------------------------------

@pytest.mark.parametrize("data,model", [(1, 8), (2, 4), (8, 1)])
def test_a2a_matches_single_and_psum(devices8, data, model):
    mesh = create_mesh(data, model, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=VOCAB)
    opt = make_optimizer({"category": "adam", "learning_rate": 0.05})
    init = {"category": "constant", "value": 0.5}
    spec = st.make_sharding_spec(meta, mesh, plane="a2a")
    pspec = st.make_sharding_spec(meta, mesh, plane="psum")
    assert spec.num_shards == mesh.size
    assert pspec.num_shards == mesh.shape["model"]

    sharded = st.create_sharded_table(meta, opt, init, mesh=mesh, spec=spec)
    psharded = st.create_sharded_table(meta, opt, init, mesh=mesh, spec=pspec)
    single = create_table(meta, opt, init, capacity=spec.padded_vocab)

    rng = np.random.RandomState(0)
    B = 32
    for step in range(3):
        # include invalid ids (negative / out of range): zero rows + dropped
        idx = rng.randint(-3, VOCAB + 3, size=B).astype(np.int32)
        grads = rng.randn(B, DIM).astype(np.float32)
        jidx, jg = jnp.asarray(idx), jnp.asarray(grads)

        got = st.pull_sharded(sharded, jidx, mesh=mesh, spec=spec)
        shard, local = spec.shard_and_local(jidx)
        phys = jnp.where((jidx >= 0) & (jidx < VOCAB),
                         shard * spec.rows_per_shard + local, -1)
        want = pull(single, phys)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        # replicated-batch (serving) path agrees
        got_r = st.pull_sharded(sharded, jidx, mesh=mesh, spec=spec,
                                batch_sharded=False)
        np.testing.assert_allclose(np.asarray(got_r), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        # psum plane agrees
        got_p = st.pull_sharded(psharded, jidx, mesh=mesh, spec=pspec)
        np.testing.assert_allclose(np.asarray(got_p), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

        sharded = st.apply_gradients_sharded(sharded, opt, jidx, jg,
                                             mesh=mesh, spec=spec)
        single = apply_gradients(single, opt, phys, jg)
        psharded = st.apply_gradients_sharded(psharded, opt, jidx, jg,
                                              mesh=mesh, spec=pspec)

    np.testing.assert_allclose(np.asarray(sharded.weights),
                               np.asarray(single.weights),
                               rtol=1e-5, atol=1e-5)
    for k in single.slots:
        np.testing.assert_allclose(np.asarray(sharded.slots[k]),
                                   np.asarray(single.slots[k]),
                                   rtol=1e-5, atol=1e-5)


def test_a2a_replicated_batch_apply(devices8):
    """batch_sharded=False apply: updates land once, not once per device."""
    mesh = create_mesh(2, 4, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=VOCAB)
    opt = make_optimizer({"category": "sgd", "learning_rate": 1.0})
    init = {"category": "constant", "value": 0.0}
    spec = st.make_sharding_spec(meta, mesh, plane="a2a")
    state = st.create_sharded_table(meta, opt, init, mesh=mesh, spec=spec)
    idx = jnp.asarray([3, 3, 7], jnp.int32)
    g = jnp.ones((3, DIM), jnp.float32)
    state = st.apply_gradients_sharded(state, opt, idx, g, mesh=mesh,
                                       spec=spec, batch_sharded=False)
    rows = st.pull_sharded(state, jnp.asarray([3, 7], jnp.int32), mesh=mesh,
                           spec=spec, batch_sharded=False)
    np.testing.assert_allclose(np.asarray(rows)[0], -2.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(rows)[1], -1.0, rtol=1e-6)


# --- hash-table parity -------------------------------------------------------

@pytest.mark.parametrize("data,model", [(2, 4), (8, 1)])
def test_a2a_hash_matches_single(devices8, data, model):
    mesh = create_mesh(data, model, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=2**63)
    opt = make_optimizer({"category": "adagrad", "learning_rate": 0.1})
    init = {"category": "constant", "value": 0.25}
    spec = sh.make_hash_sharding_spec(mesh, total_capacity=2048, plane="a2a")
    state = sh.create_sharded_hash_table(meta, opt, mesh=mesh, spec=spec)
    # ground truth: one big single-device table with the same base rng
    single = hash_lib.create_hash_table(meta, opt, capacity=2048,
                                        rng=jax.random.PRNGKey(0))

    rng = np.random.RandomState(7)
    B = 32
    for step in range(3):
        keys = (rng.randint(0, 1 << 30, size=B) * 2654435761 % (1 << 31)
                ).astype(np.int32)
        keys[1] = keys[0]  # duplicates combine
        g = rng.randn(B, DIM).astype(np.float32)
        jk, jg = jnp.asarray(keys), jnp.asarray(g)
        got = sh.pull_sharded(state, jk, init, mesh=mesh, spec=spec)
        want = hash_lib.pull(single, jk, init)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        state = sh.apply_gradients_sharded(state, opt, init, jk, jg,
                                           mesh=mesh, spec=spec)
        single = hash_lib.apply_gradients(single, opt, init, jk, jg)
        assert int(state.insert_failures) == 0

    got = sh.pull_sharded(state, jk, None, mesh=mesh, spec=spec)
    want = hash_lib.pull(single, jk, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --- adversarial skew: the exchange must be exact for ANY distribution ------

@pytest.mark.slow
@pytest.mark.parametrize("skew", ["congruent", "hotkey", "one_owner_hash"])
def test_a2a_exact_under_adversarial_skew(devices8, skew):
    """Bit-exact a2a/psum parity at DEFAULT settings under structured skew.

    The reference's exchange is exact for any key distribution
    (variable-size RPCs, EmbeddingPullOperator.cpp:60-112); the residue loop
    must make the fixed-capacity TPU exchange match: ids all congruent mod
    the shard count (every unique routed to ONE owner), hot-key floods, and
    a batch >> capacity heuristics were tuned for.
    """
    mesh = create_mesh(2, 4, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=4096)
    opt = make_optimizer({"category": "adam", "learning_rate": 0.05})
    init = {"category": "constant", "value": 0.5}
    spec = st.make_sharding_spec(meta, mesh, plane="a2a")
    pspec = st.make_sharding_spec(meta, mesh, plane="psum")
    sharded = st.create_sharded_table(meta, opt, init, mesh=mesh, spec=spec)
    psharded = st.create_sharded_table(meta, opt, init, mesh=mesh, spec=pspec)

    rng = np.random.RandomState(13)
    B = 512
    for step in range(2):
        if skew == "congruent":
            # every id ≡ 0 mod num_shards: all uniques owned by shard 0
            idx = (rng.randint(0, 4096 // spec.num_shards, size=B)
                   * spec.num_shards).astype(np.int32)
        elif skew == "hotkey":
            idx = np.where(rng.rand(B) < 0.9, 8,
                           rng.randint(0, 4096, size=B)).astype(np.int32)
        else:
            # after dedup, >capacity uniques all map to one owner via the
            # div-free mod layout: stride by num_shards from a random base
            idx = (np.arange(B) * spec.num_shards % 4096).astype(np.int32)
        grads = rng.randn(B, DIM).astype(np.float32)
        jidx, jg = jnp.asarray(idx), jnp.asarray(grads)

        got = st.pull_sharded(sharded, jidx, mesh=mesh, spec=spec)
        want = st.pull_sharded(psharded, jidx, mesh=mesh, spec=pspec)
        # planes reduce in different shard orders -> ULP-level float
        # reassociation; routing exactness (no dropped entries) is asserted
        # bit-exactly in the constant-init tests below/above
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)

        sharded = st.apply_gradients_sharded(sharded, opt, jidx, jg,
                                             mesh=mesh, spec=spec)
        psharded = st.apply_gradients_sharded(psharded, opt, jidx, jg,
                                              mesh=mesh, spec=pspec)

    # final weights identical (a2a shards over 8 devices, psum over 4 —
    # compare through a full pull of the whole vocab)
    allv = jnp.arange(4096, dtype=jnp.int32)
    wa = st.pull_sharded(sharded, allv, mesh=mesh, spec=spec,
                         batch_sharded=False)
    wp = st.pull_sharded(psharded, allv, mesh=mesh, spec=pspec,
                         batch_sharded=False)
    np.testing.assert_allclose(np.asarray(wa), np.asarray(wp),
                               rtol=1e-6, atol=1e-7)


def test_a2a_hash_exact_under_skew(devices8):
    """Hash plane: keys all congruent mod num_shards still train exactly."""
    mesh = create_mesh(2, 4, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=2**63)
    opt = make_optimizer({"category": "sgd", "learning_rate": 1.0})
    init = {"category": "constant", "value": 0.0}
    spec = sh.make_hash_sharding_spec(mesh, total_capacity=4096, plane="a2a")
    state = sh.create_sharded_hash_table(meta, opt, mesh=mesh, spec=spec)
    single = hash_lib.create_hash_table(meta, opt, capacity=4096,
                                        rng=jax.random.PRNGKey(0))
    B = 256
    # all keys owned by shard 3: key % 8 == 3, far more uniques than the
    # default bucket capacity for a 256-entry slice over 8 shards
    keys = (np.arange(B, dtype=np.int32) * spec.num_shards + 3)
    g = np.ones((B, DIM), np.float32)
    jk, jg = jnp.asarray(keys), jnp.asarray(g)
    state = sh.apply_gradients_sharded(state, opt, init, jk, jg,
                                       mesh=mesh, spec=spec)
    single = hash_lib.apply_gradients(single, opt, init, jk, jg)
    got = sh.pull_sharded(state, jk, None, mesh=mesh, spec=spec)
    want = hash_lib.pull(single, jk, None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got), -1.0, rtol=1e-6)


# --- end-to-end through the collection ---------------------------------------

def test_collection_planes_agree(devices8):
    """Same model trained on a2a and psum planes: identical states."""
    mesh = create_mesh(2, 4, devices8)

    def run(plane):
        specs = (
            EmbeddingSpec(name="bounded", input_dim=VOCAB, output_dim=DIM,
                          initializer={"category": "constant", "value": 0.1},
                          plane=plane),
            EmbeddingSpec(name="hashed", input_dim=-1, output_dim=DIM,
                          hash_capacity=1024, plane=plane),
        )
        coll = EmbeddingCollection(specs, mesh)
        states = coll.init(jax.random.PRNGKey(3))
        rng = np.random.RandomState(11)
        for _ in range(2):
            inputs = {
                "bounded": jnp.asarray(
                    rng.randint(0, VOCAB, size=16).astype(np.int32)),
                "hashed": jnp.asarray(
                    (rng.randint(0, 1 << 28, size=16) * 7919).astype(np.int32)),
            }
            rows = coll.pull(states, inputs)
            grads = {k: jnp.ones_like(v) * 0.5 for k, v in rows.items()}
            states = coll.apply_gradients(states, inputs, grads)
        rows = coll.pull(states, inputs)
        return {k: np.asarray(v) for k, v in rows.items()}

    got_a2a = run("a2a")
    got_psum = run("psum")
    for k in got_a2a:
        np.testing.assert_allclose(got_a2a[k], got_psum[k],
                                   rtol=1e-5, atol=1e-6)


def test_a2a_wide_keys_sharded_matches_single(devices8):
    """WIDE (64-bit pair, x64-off) keys through the sharded a2a plane:
    parity with a single wide table, keys spanning >2^32 with colliding
    lo words — the default-configuration full-width key space (the
    reference's 2^62 hashed ids) without a dedicated x64 process."""
    mesh = create_mesh(2, 4, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=2**63)
    opt = make_optimizer({"category": "adagrad", "learning_rate": 0.1})
    init = {"category": "constant", "value": 0.25}
    spec = sh.make_hash_sharding_spec(mesh, total_capacity=4096,
                                      plane="a2a", key_width=64)
    assert spec.wide
    state = sh.create_sharded_hash_table(meta, opt, mesh=mesh, spec=spec)
    assert state.keys.ndim == 2
    single = hash_lib.create_hash_table(meta, opt, capacity=4096,
                                        rng=jax.random.PRNGKey(0),
                                        key_width=64)

    rng = np.random.RandomState(7)
    B = 64
    for step in range(3):
        lo = rng.randint(0, 1 << 16, size=B).astype(np.int64)
        hi = rng.randint(0, 1 << 28, size=B).astype(np.int64)
        k64 = lo + (hi << 32)           # heavy lo-word collisions
        k64[1] = k64[0]                 # duplicates combine
        pairs = jnp.asarray(hash_lib.split64(k64))
        g = rng.randn(B, DIM).astype(np.float32)
        jg = jnp.asarray(g)
        got = sh.pull_sharded(state, pairs, init, mesh=mesh, spec=spec,
                              batch_sharded=False)
        want = hash_lib.pull(single, pairs, init)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        state = sh.apply_gradients_sharded(state, opt, init, pairs, jg,
                                           mesh=mesh, spec=spec,
                                           batch_sharded=False)
        single = hash_lib.apply_gradients(single, opt, init, pairs, jg)
        assert int(state.insert_failures) == 0

    got = sh.pull_sharded(state, pairs, None, mesh=mesh, spec=spec,
                          batch_sharded=False)
    want = hash_lib.pull(single, pairs, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # distinct rows for keys sharing lo words: no mod-2^32 aliasing
    probe = jnp.asarray(hash_lib.split64(
        np.asarray([42, 42 + (1 << 32)], np.int64)))
    r = sh.pull_sharded(state, probe, init, mesh=mesh, spec=spec,
                        batch_sharded=False)
    w = hash_lib.pull(single, probe, init)
    np.testing.assert_allclose(np.asarray(r), np.asarray(w), rtol=1e-6)


def test_a2a_wide_keys_exact_under_skew(devices8):
    """Wide pair keys + structured owner skew: the residue/fallback
    machinery must stay exact when every unique is owned by one shard."""
    mesh = create_mesh(2, 4, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=2**63)
    opt = make_optimizer({"category": "sgd", "learning_rate": 1.0})
    init = {"category": "constant", "value": 0.0}
    spec = sh.make_hash_sharding_spec(mesh, total_capacity=4096,
                                      plane="a2a", key_width=64)
    state = sh.create_sharded_hash_table(meta, opt, mesh=mesh, spec=spec)
    single = hash_lib.create_hash_table(meta, opt, capacity=4096,
                                        rng=jax.random.PRNGKey(0),
                                        key_width=64)
    B = 256
    # craft keys all landing on ONE owner under the (hi*2^32+lo) mod 8
    # rule: lo = 8*i, hi = 0  ->  key mod 8 == 0 for all
    k64 = np.arange(B, dtype=np.int64) * 8
    pairs = jnp.asarray(hash_lib.split64(k64))
    owners = np.asarray(spec.owner_shard(pairs))
    assert (owners == owners[0]).all()
    g = jnp.ones((B, DIM), jnp.float32)
    state = sh.apply_gradients_sharded(state, opt, init, pairs, g,
                                       mesh=mesh, spec=spec)
    single = hash_lib.apply_gradients(single, opt, init, pairs, g)
    assert int(state.insert_failures) == 0
    got = sh.pull_sharded(state, pairs, None, mesh=mesh, spec=spec)
    want = hash_lib.pull(single, pairs, None)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(np.asarray(got), -1.0, rtol=1e-6)


# --- compiled-HLO ICI contract ----------------------------------------------

def _lower_pull(mesh, plane, *, vocab=1 << 16, dim=16, batch=1024,
                use_hash=False):
    """One lowering recipe for the whole repo: delegate to the shipped
    helper (analysis/programs.py) so this file and the contract gate can
    never drift apart and audit different programs."""
    from openembedding_tpu.analysis import programs
    txt, _params = programs.lower_pull(mesh, plane, vocab=vocab, dim=dim,
                                       batch=batch, use_hash=use_hash)
    return txt


@pytest.mark.parametrize("mesh_shape", [
    (2, 4), pytest.param((1, 8), marks=pytest.mark.slow)])
@pytest.mark.parametrize("use_hash", [False, True])
def test_a2a_pull_ici_contract(devices8, mesh_shape, use_hash):
    """The compiled a2a pull program's ICI contract: the owner exchange is
    an all-to-all, and NO all-gather beyond the O(batch_slice * dim) row
    re-assembly exists — per-device bytes O(slack * slice * dim), never
    O(global_batch * dim) or O(table). Guarded in the COMPILED HLO so a
    sharding-annotation regression (XLA re-materializing tables or the
    global batch) fails loudly. Reference analogue: the exchange-not-
    broadcast design of EmbeddingPullOperator.cpp:60-112."""
    from openembedding_tpu.utils import hlocheck
    B, dim = 1024, 16
    mesh = create_mesh(*mesh_shape, devices8)
    txt = _lower_pull(mesh, "a2a", dim=dim, batch=B, use_hash=use_hash)
    summary = hlocheck.check_a2a_pull_hlo(
        txt, batch_slice=B // mesh_shape[0], dim=dim)
    assert summary["all-to-all"][0] >= 1

    # the psum baseline CARRIES the O(batch_slice * dim) broadcast-style
    # signature the a2a bound excludes — proves the bound is meaningful
    txt_psum = _lower_pull(mesh, "psum", dim=dim, batch=B,
                           use_hash=use_hash)
    psum_summary = hlocheck.summarize(txt_psum)
    assert "all-to-all" not in psum_summary
    big = [b for op, b, _largest in hlocheck.collect_collectives(txt_psum)
           if op in ("all-reduce", "all-gather")
           and b >= (B // mesh_shape[0]) * dim * 4]
    assert big, f"psum plane lost its broadcast signature: {psum_summary}"


@pytest.mark.slow
def test_a2a_pull_ici_contract_16dev():
    """Same contract on a 16-device virtual mesh (a child process: this
    process's backend is pinned to 8 devices) — the scaling regime the
    plane exists for. Slow lane: the child recompiles 8 programs from
    scratch (~several min on CPU); tier-1 keeps the same contract on the
    8-device mesh here and in test_analysis_contracts.py."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = f"""
import sys
sys.path.insert(0, {root!r})
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 16)
sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
import jax.numpy as jnp
from openembedding_tpu.parallel.mesh import create_mesh
from openembedding_tpu.utils import hlocheck
import test_alltoall as t
for shape in ((4, 4), (2, 8)):
    mesh = create_mesh(*shape)
    for use_hash in (False, True):
        txt = t._lower_pull(mesh, "a2a", dim=16, batch=2048,
                            use_hash=use_hash)
        s = hlocheck.check_a2a_pull_hlo(txt, batch_slice=2048 // shape[0],
                                        dim=16)
        print(shape, use_hash, dict(s))
print("ok")
"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "ok" in out.stdout

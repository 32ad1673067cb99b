"""Sharded-table parity: distributed pull/apply must match the single-shard core.

Mirrors the reference's multi-node matrix tests (c_api_test.h: nodes x shard
configs cross-checked against a local replica) — here the "cluster" is the
8-device CPU mesh and ground truth is the single-device table code.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu import (EmbeddingVariableMeta, apply_gradients,
                               create_table, make_optimizer, pull)
from openembedding_tpu.parallel.mesh import create_mesh
from openembedding_tpu.parallel import sharded_table as st

VOCAB, DIM = 50, 4


@pytest.mark.parametrize("layout", ["mod", "div"])
@pytest.mark.parametrize("data,model", [(1, 8), (2, 4), (8, 1)])
def test_sharded_matches_single(devices8, layout, data, model):
    mesh = create_mesh(data, model, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=VOCAB)
    opt = make_optimizer({"category": "adagrad", "learning_rate": 0.1})
    spec = st.make_sharding_spec(meta, mesh, layout=layout)

    sharded = st.create_sharded_table(meta, opt, {"category": "constant", "value": 0.5},
                                      mesh=mesh, spec=spec)
    single = create_table(meta, opt, {"category": "constant", "value": 0.5},
                          capacity=spec.padded_vocab)

    rng = np.random.RandomState(0)
    B = 16  # divisible by all data sizes
    for step in range(3):
        idx = rng.randint(0, VOCAB, size=B).astype(np.int32)
        grads = rng.randn(B, DIM).astype(np.float32)
        jidx, jg = jnp.asarray(idx), jnp.asarray(grads)

        got_rows = st.pull_sharded(sharded, jidx, mesh=mesh, spec=spec)
        # single-shard ground truth uses logical ids directly
        shard, local = spec.shard_and_local(jidx)
        phys = shard * spec.rows_per_shard + local
        want_rows = pull(single, phys)
        np.testing.assert_allclose(np.asarray(got_rows), np.asarray(want_rows),
                                   rtol=1e-6, atol=1e-6)

        sharded = st.apply_gradients_sharded(sharded, opt, jidx, jg,
                                             mesh=mesh, spec=spec)
        single = apply_gradients(single, opt, phys, jg)

    np.testing.assert_allclose(np.asarray(sharded.weights),
                               np.asarray(single.weights), rtol=1e-5, atol=1e-5)
    for k in single.slots:
        np.testing.assert_allclose(np.asarray(sharded.slots[k]),
                                   np.asarray(single.slots[k]), rtol=1e-5, atol=1e-5)


def test_batch_sharded_consistency(devices8):
    """Sharded-batch path == replicated-batch path."""
    mesh = create_mesh(4, 2, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=VOCAB)
    opt = make_optimizer({"category": "sgd", "learning_rate": 0.5, "momentum": 0.9})
    spec = st.make_sharding_spec(meta, mesh)
    t1 = st.create_sharded_table(meta, opt, mesh=mesh, spec=spec,
                                 rng=jax.random.PRNGKey(5))
    t2 = jax.tree.map(jnp.copy, t1)

    idx = jnp.arange(16, dtype=jnp.int32) % VOCAB
    g = jnp.ones((16, DIM)) * jnp.arange(16)[:, None]

    r1 = st.pull_sharded(t1, idx, mesh=mesh, spec=spec, batch_sharded=True)
    r2 = st.pull_sharded(t2, idx, mesh=mesh, spec=spec, batch_sharded=False)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), rtol=1e-6)

    t1 = st.apply_gradients_sharded(t1, opt, idx, g, mesh=mesh, spec=spec,
                                    batch_sharded=True)
    t2 = st.apply_gradients_sharded(t2, opt, idx, g, mesh=mesh, spec=spec,
                                    batch_sharded=False)
    np.testing.assert_allclose(np.asarray(t1.weights), np.asarray(t2.weights),
                               rtol=1e-6, atol=1e-6)


def test_mod_layout_spreads_hot_rows(devices8):
    """Sequential hot ids 0..7 land on 8 different shards under mod layout."""
    mesh = create_mesh(1, 8, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=2, vocabulary_size=64)
    spec = st.make_sharding_spec(meta, mesh, layout="mod")
    shard, _ = spec.shard_and_local(jnp.arange(8))
    assert sorted(np.asarray(shard).tolist()) == list(range(8))


def test_out_of_range_index_zero_row_and_dropped(devices8):
    mesh = create_mesh(1, 8, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=VOCAB)
    opt = make_optimizer({"category": "sgd", "learning_rate": 1.0})
    spec = st.make_sharding_spec(meta, mesh)
    t = st.create_sharded_table(meta, opt, {"category": "constant", "value": 1.0},
                                mesh=mesh, spec=spec)
    bad = jnp.array([spec.padded_vocab, spec.padded_vocab + 9, -1], dtype=jnp.int32)
    rows = st.pull_sharded(t, bad, mesh=mesh, spec=spec, batch_sharded=False)
    np.testing.assert_array_equal(np.asarray(rows), np.zeros((3, DIM)))
    before = np.asarray(t.weights).copy()
    t2 = st.apply_gradients_sharded(t, opt, bad, jnp.ones((3, DIM)),
                                    mesh=mesh, spec=spec, batch_sharded=False)
    np.testing.assert_array_equal(before, np.asarray(t2.weights))


def test_bfloat16_table_trains_sharded(devices8):
    """bf16 storage with f32 optimizer math, on the a2a plane end-to-end
    (the README-advertised bfloat16 path; reference stores f32/f64 only —
    bf16 halves HBM, a TPU-native win)."""
    mesh = create_mesh(2, 4, devices8)
    meta = EmbeddingVariableMeta(embedding_dim=8, vocabulary_size=128,
                                 datatype="bfloat16")
    opt = make_optimizer({"category": "adagrad", "learning_rate": 0.5})
    spec = st.make_sharding_spec(meta, mesh)
    state = st.create_sharded_table(
        meta, opt, {"category": "constant", "value": 0.25},
        mesh=mesh, spec=spec)
    assert state.weights.dtype == jnp.bfloat16
    # the at-rest precision-ladder contract (parallel/precision.py):
    # bf16 WEIGHTS halve the HBM-dominant array, optimizer SLOTS store
    # at f32 (master-statistics rule — accumulator drift in bf16 would
    # compound every step; the update math was already f32, table.py)
    assert all(s.dtype == jnp.float32
               for s in jax.tree.leaves(state.slots))
    idx = jnp.asarray(np.arange(16, dtype=np.int32))
    for _ in range(3):
        rows = st.pull_sharded(state, idx, mesh=mesh, spec=spec,
                               batch_sharded=False)
        assert rows.dtype == jnp.bfloat16
        g = jnp.ones((16, 8), jnp.bfloat16) * 0.5
        state = st.apply_gradients_sharded(state, opt, idx, g, mesh=mesh,
                                           spec=spec, batch_sharded=False)
    rows = np.asarray(st.pull_sharded(state, idx, mesh=mesh, spec=spec,
                                      batch_sharded=False)).astype(np.float32)
    # weights moved (adagrad with constant grads): must differ from init
    # and be finite, identical across the batch (same update everywhere)
    assert np.isfinite(rows).all()
    assert (rows < 0.25 - 0.1).all()
    np.testing.assert_allclose(rows, np.broadcast_to(rows[0], rows.shape),
                               rtol=1e-2)


# --- one builder, two stores (parallel/sharded.py) ---------------------------

def _table_of(kind, mesh):
    """(state, store, the kind's own pull, the kind's own apply, keys) of a
    small table of ``kind`` on ``mesh``."""
    from openembedding_tpu import hash_table as hash_lib
    from openembedding_tpu.optim.initializers import make_initializer
    from openembedding_tpu.parallel import sharded_hash as sh

    opt = make_optimizer({"category": "adagrad", "learning_rate": 0.1})
    ids = np.random.RandomState(7).randint(0, VOCAB, size=16)
    if kind == "array":
        meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=VOCAB)
        spec = st.make_sharding_spec(meta, mesh)
        state = st.create_sharded_table(meta, opt, mesh=mesh, spec=spec,
                                        rng=jax.random.PRNGKey(3))

        def pull(s, k):
            return st.pull_sharded(s, k, mesh=mesh, spec=spec)

        def apply(s, k, g):
            return st.apply_gradients_sharded(s, opt, k, g, mesh=mesh,
                                              spec=spec)
        return (state, st.ArrayStore(spec), pull, apply,
                jnp.asarray(ids, jnp.int32))

    wide = kind == "hashwide"
    init = make_initializer({"category": "uniform", "minval": -1.0,
                             "maxval": 1.0})
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=2**62)
    spec = sh.make_hash_sharding_spec(mesh, 1024,
                                      key_width=64 if wide else 32)
    state = sh.create_sharded_hash_table(meta, opt, mesh=mesh, spec=spec,
                                         rng=jax.random.PRNGKey(3))
    keys = ids.astype(np.int64) * 1_000_003 + 17
    keys = hash_lib.split64(keys * (1 << 20)) if wide \
        else keys.astype(np.int32)

    def pull(s, k):
        return sh.pull_sharded(s, k, init, mesh=mesh, spec=spec)

    def apply(s, k, g):
        return sh.apply_gradients_sharded(s, opt, init, k, g, mesh=mesh,
                                          spec=spec)
    return state, sh.HashStore(spec, init), pull, apply, jnp.asarray(keys)


@pytest.mark.parametrize("data,model", [(1, 1), (2, 2)],
                         ids=["masked_local", "routed"])
@pytest.mark.parametrize("kind", ["array", "hash32", "hashwide"])
def test_both_kinds_through_the_one_builder(devices8, kind, data, model):
    """pull -> apply_gradients -> pull through ``sharded``'s entry equals
    the per-kind public names bit for bit, and each program is built once
    per (mesh, store, ...) however the state is wrapped."""
    from openembedding_tpu.parallel import precision, sharded

    mesh = create_mesh(data, model, devices8[:data * model])
    state, store, pull_kind, apply_kind, keys = _table_of(kind, mesh)
    assert store.spec.routes == (data * model > 1)
    grads = jnp.asarray(np.random.RandomState(8).randn(16, DIM), jnp.float32)
    opt = make_optimizer({"category": "adagrad", "learning_rate": 0.1})
    sharded._pull_program.cache_clear()
    sharded._apply_program.cache_clear()

    def through_builder(s):
        rows0 = sharded.pull_sharded(s, keys, mesh=mesh, store=store)
        s = sharded.apply_gradients_sharded(s, opt, keys, grads, mesh=mesh,
                                            store=store)
        return rows0, s, sharded.pull_sharded(s, keys, mesh=mesh, store=store)

    def through_kind(s):
        rows0 = pull_kind(s, keys)
        s = apply_kind(s, keys, grads)
        return rows0, s, pull_kind(s, keys)

    got, want = through_builder(state), through_kind(state)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.abs(np.asarray(got[2]) - np.asarray(got[0])).max() > 0

    # a state in the int8-EF wrapper reads through precision.unwrap: the
    # same table, the same program
    wrapped = precision.empty_ef(state, dim=DIM, **store.ef_space(state))
    np.testing.assert_array_equal(
        np.asarray(sharded.pull_sharded(wrapped, keys, mesh=mesh,
                                        store=store)), np.asarray(got[0]))
    assert sharded._pull_program.cache_info().misses == 1
    assert sharded._apply_program.cache_info().misses == 1


@pytest.mark.parametrize("capacity,spilled", [(0, 0), (1, 1)],
                         ids=["fits", "spills"])
def test_a_routed_step_counts_what_a_hand_count_says(devices8, capacity,
                                                     spilled):
    """Eight ids over data 2 x model 2, two a device (the ``mod`` layout:
    owner ``id % 4``): device 0 asks for {0}, 1 for {4, 8}, 2 for {4} and
    an id no shard owns, 3 for {9}. Plan, pull and push through the
    builder, under ``record_stats``: five distinct keys are sent (four
    where a bucket holds one key and 8 is left for the residue round and
    the gathered branch, which ``a2a_extra_entries_*`` count as they do
    without a plan); the owners hold four distinct keys of the 32 bucket
    slots they received (16 at capacity 1), read a row each and hand the
    push a row each; rows and table are what they are without a plan."""
    from openembedding_tpu.parallel import sharded
    from openembedding_tpu.utils import observability as obs

    mesh = create_mesh(2, 2, devices8[:4])
    meta = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=VOCAB)
    opt = make_optimizer({"category": "adagrad", "learning_rate": 0.1})
    spec = st.make_sharding_spec(meta, mesh, a2a_capacity=capacity)
    store = st.ArrayStore(spec)
    state = st.create_sharded_table(meta, opt, mesh=mesh, spec=spec,
                                    rng=jax.random.PRNGKey(3))
    ids = jnp.asarray([0, 0, 8, 4, 4, -1, 9, 9], jnp.int32)
    grads = jnp.asarray(np.random.RandomState(8).randn(8, DIM), jnp.float32)
    want_rows = sharded.pull_sharded(state, ids, mesh=mesh, store=store)
    want = sharded.apply_gradients_sharded(state, opt, ids, grads, mesh=mesh,
                                           store=store)
    programs = (sharded._plan_program, sharded._pull_program,
                sharded._apply_program)
    obs.GLOBAL.reset()
    obs.set_evaluate_performance(True)
    try:
        plan = sharded.plan_sharded(ids, mesh=mesh, store=store)
        rows, resolved = sharded.pull_sharded(state, ids, mesh=mesh,
                                              store=store, plan=plan)
        got = sharded.apply_gradients_sharded(
            state, opt, ids, grads, mesh=mesh, store=store, plan=plan,
            resolved=resolved)
        jax.block_until_ready(got)
        jax.effects_barrier()
        counted = {k: int(v["count"])       # (plane timings hold no count)
                   for k, v in obs.GLOBAL.snapshot().items() if "count" in v}
    finally:
        obs.set_evaluate_performance(False)
        obs.GLOBAL.reset()
        for program in programs:        # the recording programs
            program.cache_clear()
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(want_rows))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    cap = capacity or 2
    assert int(plan.spilled) == spilled
    assert {k: counted[k] for k in counted if k.startswith("routed_plan")} \
        == {"routed_plan_keys_sent": 5 - spilled,
            "routed_plan_owner_keys_live": 4 - spilled,
            "routed_plan_owner_slots": 4 * 4 * cap}
    assert counted["a2a_extra_entries_pull"] == spilled
    assert counted.get("a2a_extra_entries_push", 0) == spilled
    # the owners' read of round 1: a row a distinct key, for 4 * cap
    # positions a device
    assert counted["pull_keys_live"] == 4 - spilled
    assert counted["pull_positions"] == 4 * 4 * cap
    # the apply took every live row from the pull: the owner's read, or
    # the gathered branch's own where the step spilled (4 distinct keys)
    assert counted["push_rows_carried"] == counted["apply_slots_live"] == 4

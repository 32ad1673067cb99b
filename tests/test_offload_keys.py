"""The offload tier over an unbounded key space (``ShardedOffloadedTable``
built without a ``vocab``): 62-bit keys through a keyed host store behind
a wide-key HBM cache, fresh keys born in the step. Held, bit for bit, to
the all-in-HBM hash table of the same keys and to a plain dict reference
(key -> (weights, accumulator), Adagrad in float32 numpy, no cache, no
tier), over steps that fetch, meet fresh keys, evict, flush, persist and
restore."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu import EmbeddingVariableMeta
from openembedding_tpu import hash_table as hash_lib
from openembedding_tpu import offload_keys as keys_lib
from openembedding_tpu.analysis import scope
from openembedding_tpu.offload import ShardedOffloadedTable
from openembedding_tpu.parallel import sharded_hash as sh

DIM = 4
ADAGRAD = {"category": "adagrad", "learning_rate": 0.1,
           "initial_accumulator_value": 0.1, "epsilon": 1e-7}
# a power of two: the draw's scaling is exact, so a compiler that fuses it
# into the row's first update (one rounding less) changes no bit, and the
# numpy reference can follow
FRESH = {"category": "normal", "mean": 0.0, "stddev": 2.0 ** -7}
NAMES = ("fields", "fields:linear")     # the fused form the models read


def _mesh(devices8, shape):
    from openembedding_tpu.parallel.mesh import create_mesh
    data, model = shape
    return create_mesh(data, model, devices8[:data * model])


def _tiers(mesh, cache=256, **kw):
    return {name: ShardedOffloadedTable(
        name, EmbeddingVariableMeta(embedding_dim=dim, vocabulary_size=-1),
        ADAGRAD, FRESH, cache_capacity=cache, mesh=mesh, **kw)
        for name, dim in zip(NAMES, (DIM, 1))}


def _universe(n, seed=0):
    """``n`` distinct 62-bit keys, a few with words that are NaNs' bits or
    negative: a key word is any 32 bits."""
    rng = np.random.RandomState(seed)
    keys = rng.randint(0, 1 << 62, size=n, dtype=np.int64)
    keys[0] = (0x7FC00001 << 32) | 0x7F800001      # NaN patterns, both words
    keys[1] = (0x3FF00000 << 32) | 0xFFC00000      # low word a negative NaN
    keys[2] = -12345                               # a negative key
    return np.unique(keys)


def _stored_rows(keys, dim, seed):
    rng = np.random.RandomState(seed)
    return (rng.standard_normal((len(keys), dim)) * 0.05).astype(np.float32)


def _batches(universe, n, width=3, batch=32, seed=1, walk=24):
    """Batches of ``[B, width, 2]`` pair columns whose keys walk through
    the universe: every batch brings keys the ones before did not."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        lo = (i * walk) % max(1, len(universe) - 4 * walk)
        keys = universe[rng.randint(lo, lo + 4 * walk, (batch, width))]
        col = hash_lib.split64(keys)                # [B, width, 2] int32
        out.append({"label": (keys[:, 0] % 2).astype(np.float32),
                    "dense": None, "keys": keys,
                    "sparse": {NAMES[0]: col, NAMES[1]: col}})
    return out


def _trainer(mesh, tiers=None, cache=256, depth=2):
    """DeepFM-shaped trainer over ``off`` / ``off:linear``: behind the
    keyed tier, or (no tiers) all in HBM hash tables of the same specs."""
    import optax
    from openembedding_tpu import EmbeddingCollection, Trainer
    from openembedding_tpu.embedding import EmbeddingSpec
    from openembedding_tpu.models import deepctr
    if tiers is not None:
        specs = [t.embedding_spec() for t in tiers.values()]
    else:
        specs = [EmbeddingSpec(name=name, input_dim=-1, output_dim=dim,
                               optimizer=ADAGRAD, initializer=FRESH,
                               hash_capacity=cache)
                 for name, dim in zip(NAMES, (DIM, 1))]
    coll = EmbeddingCollection(specs, mesh)
    model = deepctr.WideDeep(feature_names=("a", "b", "c"), dnn_units=(8,))
    return Trainer(model, coll, optax.sgd(0.1), offload=tiers,
                   pipeline_depth=depth)


def _table_rows(state, keys, mesh, spec):
    """(found, weights, accum) a hash table state holds for int64 keys."""
    found, w, slots = jax.device_get(sh.read_rows_sharded(
        state, jnp.asarray(hash_lib.split64(keys)), mesh=mesh, spec=spec))
    return np.asarray(found), np.asarray(w), np.asarray(slots["accum"])


def _store_rows(tier, keys):
    rows = tier.rows_of(keys)
    assert (rows >= 0).all()
    assert not tier._unborn[rows].any()
    return tier.host_weights[rows], tier.host_slots["accum"][rows]


def _bits(a):
    return np.ascontiguousarray(a).view(np.uint32)


def _flush(tiers, state):
    for name, tier in tiers.items():
        tier.flush(state.emb[name])
        tier.finish()


@pytest.mark.parametrize("depth", [1, 3])
def test_fit_over_keys_equals_the_all_in_hbm_hash_table(devices8, tmp_path,
                                                        depth):
    """``Trainer.fit(offload=)`` over a keyed tier whose cache evicts,
    against the same model over hash tables that hold everything: every
    key's row and accumulator in every bit after flush, the dense net
    too; then persist, restore into a new tier, and the store again."""
    mesh = _mesh(devices8, (1, 1))
    universe = _universe(600)
    known = universe[::2]           # half the keys are stored at the start
    batches = _batches(universe, 14)

    tiers = _tiers(mesh, cache=256)
    big = _trainer(mesh, cache=4096)
    small = _trainer(mesh, tiers, depth=depth)
    s_big = big.init(jax.random.PRNGKey(0), big.shard_batch(batches[0]))
    s_small = small.init(jax.random.PRNGKey(0),
                         small.shard_batch(batches[0]))
    emb = dict(s_big.emb)
    for n, (name, tier) in enumerate(tiers.items()):
        rows = _stored_rows(known, tier.meta.embedding_dim, seed=10 + n)
        tier.load_rows(known, rows)
        emb[name] = sh.insert_rows_sharded(
            emb[name], jnp.asarray(hash_lib.split64(known)),
            jnp.asarray(rows), mesh=mesh,
            spec=big.collection.sharding_spec(name))
        assert np.array_equal(np.asarray(s_small.emb[name].init_rng),
                              np.asarray(emb[name].init_rng))
    s_big = s_big.replace(emb=emb)

    s_big, m_big = big.fit(s_big, batches)
    s_small, m_small = small.fit(s_small, batches)
    _flush(tiers, s_small)
    assert float(m_big["loss"]) == float(m_small["loss"])
    for a, b in zip(jax.tree.leaves(s_big.params),
                    jax.tree.leaves(s_small.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    seen = np.unique(np.concatenate([b["keys"].ravel() for b in batches]))
    fresh = np.setdiff1d(seen, known)
    assert fresh.size > 50
    for name, tier in tiers.items():
        assert tier.evictions > 0           # the cache really turned over
        found, w, accum = _table_rows(
            s_big.emb[name], seen, mesh, big.collection.sharding_spec(name))
        assert found.all()
        sw, sa = _store_rows(tier, seen)
        np.testing.assert_array_equal(_bits(sw), _bits(w))
        np.testing.assert_array_equal(_bits(sa), _bits(accum))
        # a stored key no batch touched keeps the row it was loaded with
        idle = np.setdiff1d(known, seen)
        np.testing.assert_array_equal(
            tier.host_weights[tier.rows_of(idle)],
            _stored_rows(known, tier.meta.embedding_dim,
                         seed=10 + NAMES.index(name))[
                np.searchsorted(known, idle)])
        assert int(s_small.emb[name].insert_failures) == 0

    # persist, then a NEW tier restores: same keys, same rows
    restored = _tiers(mesh, cache=256)
    for name, tier in tiers.items():
        tier.persist(s_small.emb[name], str(tmp_path / name))
        cache = restored[name].restore(str(tmp_path / name))
        assert int(jnp.sum(cache.keys[:, 1] != hash_lib.empty_key(jnp.int32))
                   ) == 0
        every = np.union1d(seen, known)
        assert restored[name]._index.rows == every.size
        for got, want in zip(_store_rows(restored[name], every),
                             _store_rows(tier, every)):
            np.testing.assert_array_equal(_bits(got), _bits(want))


class _DictReference:
    """key -> (weights, accumulator); Adagrad in float32 numpy."""

    def __init__(self, dim, fresh_row):
        self.rows, self.dim, self.fresh_row = {}, dim, fresh_row

    def apply(self, keys, grads):
        lr, eps = np.float32(ADAGRAD["learning_rate"]), \
            np.float32(ADAGRAD["epsilon"])
        summed = {}
        for k, g in zip(keys.tolist(), grads):
            summed[k] = summed[k] + g if k in summed else g.copy()
        for k, g in summed.items():
            if k not in self.rows:
                self.rows[k] = (self.fresh_row(k), np.full(
                    self.dim, ADAGRAD["initial_accumulator_value"],
                    np.float32))
            w, a = self.rows[k]
            a = a + g * g
            self.rows[k] = (w - lr * g / (np.sqrt(a) + eps), a)


@pytest.mark.parametrize("shape", [(1, 1), (2, 4)])
def test_keyed_tier_equals_the_plain_dict_reference(devices8, tmp_path,
                                                    shape):
    """The tables driven by hand (prepare, push, note) with gradients
    whose sums are exact in float32, on one device and on a 2x4 mesh (the
    wide-key insert and read across shards): the store after evictions,
    a flush, a persist and a restore is the dict reference's rows."""
    from openembedding_tpu import EmbeddingCollection
    mesh = _mesh(devices8, shape)
    universe = _universe(500, seed=3)
    known = universe[1::3]
    tiers = _tiers(mesh, cache=256)
    tier = tiers[NAMES[0]]
    coll = EmbeddingCollection([tier.embedding_spec()], mesh)
    emb = coll.init(jax.random.PRNGKey(7))
    rows0 = _stored_rows(known, DIM, seed=5)
    tier.load_rows(known, rows0)

    init_rng = emb[NAMES[0]].init_rng
    initializer = coll.initializer(NAMES[0])

    # the hash table's own rule for a key it has not seen, per key
    drawn = np.asarray(hash_lib.init_rows(
        initializer, init_rng, jnp.asarray(hash_lib.split64(universe)), DIM,
        jnp.float32))
    fresh_row = lambda key: drawn[np.searchsorted(universe, key)]

    ref = _DictReference(DIM, fresh_row)
    for k, r in zip(known.tolist(), rows0):
        ref.rows[k] = (r, np.full(DIM, 0.1, np.float32))

    push = jax.jit(lambda emb, idx, g: coll.apply_gradients(
        emb, {NAMES[0]: idx}, {NAMES[0]: g}, batch_sharded=False))
    rng = np.random.RandomState(11)
    touched = []
    for b in _batches(universe, 12, width=2, batch=16, seed=4, walk=30):
        col = b["sparse"][NAMES[0]]
        grads = (rng.randint(-8, 9, col.shape[:2] + (DIM,))
                 * 2.0 ** -6).astype(np.float32)
        emb[NAMES[0]] = tier.prepare(emb[NAMES[0]], col)
        emb = push(emb, jnp.asarray(col), jnp.asarray(grads))
        tier.note_update(col)
        ref.apply(b["keys"].ravel(), grads.reshape(-1, DIM))
        touched.append(b["keys"].ravel())
    assert tier.evictions > 0
    tier.flush(emb[NAMES[0]])
    tier.finish()
    assert int(emb[NAMES[0]].insert_failures) == 0

    def check(t):
        keys = np.asarray(sorted(ref.rows), np.int64)
        assert t._index.rows == keys.size
        w, a = _store_rows(t, keys)
        np.testing.assert_array_equal(
            _bits(w), _bits(np.stack([ref.rows[k][0] for k in keys.tolist()])))
        np.testing.assert_array_equal(
            _bits(a), _bits(np.stack([ref.rows[k][1] for k in keys.tolist()])))

    check(tier)
    tier.persist(emb[NAMES[0]], str(tmp_path / "p"))
    again = _tiers(mesh, cache=256)[NAMES[0]]
    again.restore(str(tmp_path / "p"))
    check(again)
    assert np.setdiff1d(np.concatenate(touched), known).size > 50


def test_store_and_index_grow_across_block_boundaries(devices8, monkeypatch):
    """A store of 64-row blocks and an index of 64 slots grow many times
    over; nothing stored moves or changes, rows are handed out in order of
    first sight, and the memory ledger counts index and keys."""
    monkeypatch.setattr(keys_lib, "STORE_BLOCK", 64)
    monkeypatch.setattr(keys_lib, "INDEX_START", 64)
    mesh = _mesh(devices8, (1, 1))
    tier = _tiers(mesh, cache=1024)[NAMES[0]]
    universe = _universe(1000, seed=9)
    first = tier.host_weights.blocks
    grows0 = scope.HISTOGRAMS.count(scope._hist_name("offload.store_grow"),
                                    table=tier.name)
    for lo in range(0, 1000, 100):
        keys = universe[lo:lo + 100]
        tier.load_rows(keys, _stored_rows(keys, DIM, seed=lo))
        if lo == 0:
            held = [b for b in tier.host_weights.blocks]
    assert tier._index.rows == 1000
    assert len(tier.host_weights) >= 1000 and len(first) >= 16
    assert all(a is b for a, b in zip(held, tier.host_weights.blocks))
    assert scope.HISTOGRAMS.count(scope._hist_name("offload.store_grow"),
                                  table=tier.name) > grows0
    np.testing.assert_array_equal(tier.rows_of(universe), np.arange(1000))
    for lo in range(0, 1000, 100):
        np.testing.assert_array_equal(
            tier.host_weights[tier.rows_of(universe[lo:lo + 100])],
            _stored_rows(universe[lo:lo + 100], DIM, seed=lo))
    stats = tier.memory_stats()
    assert stats["store_rows"] == 1000
    assert stats["index_bytes"] == tier._index.nbytes > 0
    assert stats["key_bytes"] >= 1000 * 9
    assert 0 < stats["index_load"] <= keys_lib.MAX_LOAD
    # fresh keys of a prepare cross a block boundary as well
    cache = tier.create_cache()
    fresh = np.setdiff1d(_universe(300, seed=77), universe)
    prep = tier.host_prepare(fresh)
    assert prep.fresh.size == fresh.size and prep.missing.size == 0
    assert tier._index.rows == 1000 + fresh.size <= len(tier._unborn)
    tier.cancel_prepared(prep)


def test_tables_on_one_column_share_one_distinct_pass(devices8, monkeypatch):
    """``fields`` and ``fields:linear`` read the same column: the lookahead
    thread makes its ids distinct once a batch, not once a table."""
    mesh = _mesh(devices8, (1, 1))
    tiers = _tiers(mesh, cache=1024)
    trainer = _trainer(mesh, tiers)
    batches = _batches(_universe(300), 4)
    calls = []
    for tier in tiers.values():
        distinct = tier.distinct
        monkeypatch.setattr(
            tier, "distinct",
            lambda ids, distinct=distinct, name=tier.name:
            calls.append(name) or distinct(ids))
    state = trainer.init(jax.random.PRNGKey(0),
                         trainer.shard_batch(batches[0]))
    state, _ = trainer.fit(state, batches)
    assert len(calls) == len(batches)
    # a table fed its own copy of the column makes its own pass
    calls.clear()
    own = dict(batches[0], sparse={
        NAMES[0]: batches[0]["sparse"][NAMES[0]],
        NAMES[1]: batches[0]["sparse"][NAMES[1]].copy()})
    trainer.train_step(state, own)
    assert sorted(calls) == sorted(NAMES)


def test_a_companion_shares_the_key_space_and_a_step_walks_it_once(devices8):
    """``fields`` and its ``:linear`` companion hold one index: a batch's
    keys are found (and the fresh ones placed) once for both, a key's
    store row is the same in both, each has its own rows and books, and
    the pair trains to the bits of two tables with an index each."""
    mesh = _mesh(devices8, (1, 1))
    universe = _universe(400, seed=41)
    batches = _batches(universe, 6)

    def pair(shared):
        tiers = _tiers(mesh, cache=1024)
        if shared:
            first = tiers[NAMES[0]]
            tiers[NAMES[1]] = first.companion(
                NAMES[1], EmbeddingVariableMeta(embedding_dim=1,
                                                vocabulary_size=-1))
        trainer = _trainer(mesh, tiers)
        state = trainer.init(jax.random.PRNGKey(0),
                             trainer.shard_batch(batches[0]))
        return tiers, trainer, state

    tiers, trainer, state = pair(shared=True)
    a, b = tiers.values()
    assert a._index is b._index and a._keys is b._keys
    assert b.cache_capacity == a.cache_capacity and b.keyed
    assert a.memory_stats()["index_bytes"] > 0 == \
        b.memory_stats()["index_bytes"]
    walks = []
    walk = a._index.find_or_insert
    a._index.find_or_insert = lambda keys: walks.append(keys.size) \
        or walk(keys)
    state, _ = trainer.fit(state, batches)
    assert len(walks) == len(batches)           # not one a table
    _flush(tiers, state)
    seen = np.unique(np.concatenate([x["keys"].ravel() for x in batches]))
    np.testing.assert_array_equal(a.rows_of(seen), b.rows_of(seen))

    apart, trainer2, state2 = pair(shared=False)
    state2, _ = trainer2.fit(state2, batches)
    _flush(apart, state2)
    for name in NAMES:
        for got, want in zip(_store_rows(tiers[name], seen),
                             _store_rows(apart[name], seen)):
            np.testing.assert_array_equal(_bits(got), _bits(want))
    # a companion made late grows into the rows the keys already took
    late = a.companion("late", EmbeddingVariableMeta(embedding_dim=2,
                                                     vocabulary_size=-1))
    assert len(late._unborn) == len(a._unborn) >= seen.size
    assert late._unborn[late.rows_of(seen)].all()
    with pytest.raises(ValueError, match="no index to share"):
        ShardedOffloadedTable(
            "b", EmbeddingVariableMeta(embedding_dim=DIM,
                                       vocabulary_size=64),
            ADAGRAD, vocab=64, cache_capacity=64, mesh=mesh).companion(
                "c", EmbeddingVariableMeta(embedding_dim=1,
                                           vocabulary_size=64))


def test_cancel_and_stale_generation_with_fresh_keys_in_flight(devices8):
    """A cancelled prepare leaves its fresh keys unborn (their next prepare
    finds them fresh again, at the same store rows); a prepare gone stale
    under an eviction is recomputed at its apply, fresh keys included."""
    mesh = _mesh(devices8, (1, 1))
    tier = _tiers(mesh, cache=256)[NAMES[0]]
    cache = tier.create_cache()
    universe = _universe(400, seed=21)
    known = universe[:100]
    tier.load_rows(known, _stored_rows(known, DIM, seed=1))
    batch = np.concatenate([known[:20], universe[100:130]])

    prep = tier.host_prepare(batch)
    assert prep.missing.size == 20 and prep.fresh.size == 30
    assert tier._planned_count == 50
    tier.cancel_prepared(prep)
    assert tier._planned_count == 0 and tier._resident_count == 0
    again = tier.host_prepare(batch)
    np.testing.assert_array_equal(np.sort(again.fresh), np.sort(prep.fresh))
    assert tier._index.rows == 130

    # an eviction rebuilds residency under the prepare in flight
    other = tier.host_prepare(universe[130:160])       # planned behind it
    with tier._book:
        cache = tier._evict(cache, protect=np.zeros(0, np.int64),
                            budget=int(0.7 * 256), incoming=0)
    assert again.gen != tier._gen
    retries = tier.gen_retries
    cache = tier.apply_prepared(cache, again)
    assert tier.gen_retries > retries
    assert tier._resident_count == 50 and tier._planned_count == 0
    rows = tier.rows_of(batch)
    assert tier._resident[rows].all()
    assert tier._dirty.mask_rows(again.fresh).all()    # owed a write-back
    cache = tier.apply_prepared(cache, other)          # stale too: redone
    assert tier._resident_count == 80
    # the stored keys were copied in; the fresh ones are the step's to make
    found, w, _ = _table_rows(cache, batch, mesh, tier.spec)
    assert found[:20].all() and not found[20:].any()
    np.testing.assert_array_equal(w[:20], _stored_rows(known, DIM, 1)[:20])
    # pulled alone (no step): a flush finds no row, the key stays unborn,
    # an eviction drops it and it is fresh again
    assert tier.flush(cache) == 60          # ``other``'s thirty as well
    tier.finish()
    assert tier._unborn[again.fresh].all()
    with tier._book:
        cache = tier._evict(cache, protect=np.zeros(0, np.int64),
                            budget=int(0.7 * 256), incoming=0)
    assert not tier._resident[again.fresh].any()
    assert tier.host_prepare(batch).fresh.size == 30


@pytest.mark.parametrize("packed", [True, False])
def test_wide_insert_takes_any_key_bits(devices8, packed, monkeypatch):
    """The between-steps insert of a keyed tier, packed (one int32 buffer,
    both key words in it) or not: rows land under their keys, words that
    are NaNs' bits included, and read back through the write-back's read."""
    mesh = _mesh(devices8, (2, 4))
    tier = _tiers(mesh, cache=1024)[NAMES[0]]
    if not packed:
        monkeypatch.setattr(tier, "_packed_layout", lambda key_dtype: None)
    keys = _universe(200, seed=31)
    rows = _stored_rows(keys, DIM, seed=2)
    accum = np.abs(_stored_rows(keys, DIM, seed=3)) + 0.1
    tier.load_rows(keys, rows, {"accum": accum})
    cache = tier.warm(tier.create_cache(), keys)
    found, w, a = _table_rows(cache, keys, mesh, tier.spec)
    assert found.all() and int(cache.insert_failures) == 0
    np.testing.assert_array_equal(_bits(w), _bits(rows))
    np.testing.assert_array_equal(_bits(a), _bits(accum))
    assert not _table_rows(cache, np.setdiff1d(_universe(50, seed=99), keys),
                           mesh, tier.spec)[0].any()


def test_a_tier_without_vocab_is_keyed_and_says_so(devices8):
    mesh = _mesh(devices8, (1, 1))
    tier = _tiers(mesh)[NAMES[0]]
    assert tier.keyed and tier.vocab == -1
    spec = tier.embedding_spec()
    assert spec.key_dtype == "wide" and spec.input_dim == -1
    assert tier.spec.wide and tier.create_cache().keys.shape[-1] == 2
    bounded = ShardedOffloadedTable(
        "b", EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=64),
        ADAGRAD, vocab=64, cache_capacity=64, mesh=mesh)
    assert not bounded.keyed
    assert bounded.embedding_spec().key_dtype == "int32"
    assert "index_bytes" not in bounded.memory_stats()
    with pytest.raises(ValueError, match="each key once"):
        tier.load_rows(np.asarray([5, 3, 5], np.int64),
                       np.zeros((3, DIM), np.float32))
    with pytest.raises(ValueError, match="EMPTY"):
        tier.load_rows(np.asarray([keys_lib.EMPTY], np.int64),
                       np.zeros((1, DIM), np.float32))


@pytest.mark.parametrize("form", ["pairs", "int64", "int32"])
def test_distinct_reads_every_key_form(devices8, form):
    mesh = _mesh(devices8, (1, 1))
    tier = _tiers(mesh)[NAMES[0]]
    keys = np.asarray([[7, -3, 9], [7, 1 << 40, 9]], np.int64)
    if form == "pairs":
        col = hash_lib.split64(keys)
        col = np.concatenate([col, np.full((1, 3, 2), hash_lib.empty_key(
            np.int32), np.int32)])                          # padding
        want = [-3, 7, 9, 1 << 40]
    elif form == "int64":
        col, want = keys, [-3, 7, 9, 1 << 40]
    else:
        col = np.asarray([[7, -3, 9], [7, np.iinfo(np.int32).min, 9]],
                         np.int32)
        want = [-3, 7, 9]
    got = tier.distinct(col)
    assert got.dtype == np.int64 and got.tolist() == want
    assert tier.lookups_of(col) == (9 if form == "pairs" else 6)


# --- the host index and the block arrays, alone ----------------------------

@pytest.mark.parametrize("start", [64, 1 << 12])
def test_key_index_finds_what_it_placed(monkeypatch, start):
    monkeypatch.setattr(keys_lib, "INDEX_START", start)
    index = keys_lib.KeyIndex()
    rng = np.random.RandomState(0)
    held = {}
    for _ in range(6):
        keys = np.unique(rng.randint(0, 1 << 62, 3000, dtype=np.int64))
        keys = np.concatenate([keys[:50] * 0 + np.asarray(
            list(held)[:50] or keys[:50], np.int64)[:50], keys[50:]])
        keys = np.unique(keys)
        rows = index.find_or_insert(keys)
        for k, r in zip(keys.tolist(), rows.tolist()):
            assert held.setdefault(k, r) == r
    assert index.rows == len(held) and index.load <= keys_lib.MAX_LOAD
    keys = np.asarray(list(held), np.int64)
    np.testing.assert_array_equal(index.find(keys), list(held.values()))
    assert sorted(held.values()) == list(range(len(held)))
    assert (index.find(rng.randint(-(1 << 62), 0, 500)) == -1).all()
    assert index.probes > 0
    # keys that start at one slot: contenders take turns
    clash = np.arange(5000, dtype=np.int64) * index.slots
    rows = index.find_or_insert(clash[1:])
    np.testing.assert_array_equal(index.find(clash[1:]), rows)


@pytest.mark.parametrize("tail,dtype", [((), bool), ((), np.int64),
                                        ((3,), np.float32)])
def test_block_array_reads_and_writes_like_a_flat_array(tail, dtype):
    made = []

    def alloc(number, shape):
        made.append(number)
        return np.zeros(shape, dtype)

    # a first block of 4 rows, doubling up to 16 a block
    blocks = keys_lib.BlockArray(tail, dtype, keys_lib.BlockLayout(16, 4),
                                 alloc)
    blocks.grow(70)
    assert [len(b) for b in blocks.blocks] == [4, 4, 8, 16, 16, 16, 16]
    assert len(blocks) == 80 and made == list(range(7))
    flat = np.zeros((80,) + tail, dtype)
    rng = np.random.RandomState(1)
    for _ in range(5):
        rows = rng.permutation(80)[:37]
        values = rng.randint(0, 2, (37,) + tail).astype(dtype)
        blocks[rows] = values
        flat[rows] = values
    blocks[np.asarray([3, 40])] = 1
    flat[[3, 40]] = 1
    np.testing.assert_array_equal(np.asarray(blocks), flat)
    rows = rng.randint(0, 80, 200)
    np.testing.assert_array_equal(blocks[rows], flat[rows])
    np.testing.assert_array_equal(blocks[rows], flat[rows])  # places kept
    assert blocks.layout._kept[0][0] is rows
    np.testing.assert_array_equal(blocks[rows[:0]], flat[rows[:0]])
    assert blocks[np.int64(40)].tolist() == flat[40].tolist()
    assert blocks.nbytes == flat.nbytes and blocks.shape == flat.shape
    held = list(blocks.blocks)
    blocks.grow(100)
    assert all(a is b for a, b in zip(held, blocks.blocks))
    blocks[:] = 0
    assert not np.asarray(blocks).any()
    with pytest.raises(ValueError):
        keys_lib.BlockLayout(24)
    whole = keys_lib.BlockArray(tail, dtype, keys_lib.BlockLayout(16, 4096),
                                alloc)
    whole.grow(40)                  # never a first block past the cap
    assert [len(b) for b in whole.blocks] == [16, 16, 16]

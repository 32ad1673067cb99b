"""Saves while it trains: the row-exact dirty set, the snapshot in the
step's stream and the commit off the step thread, through
``Trainer.fit(autosave_every=, autosave_dir=)``.

What a save holds is fixed at its snapshot: an entry written while later
steps re-touch its rows equals the table at the entry's step. The chain
is read back three ways that have to agree bit for bit: the program's own
replay (``load_checkpoint``), the benchmark's plain numpy reference
(``benchmark/reference_chain.py``, which imports nothing of the program)
and the live table.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference_chain
from openembedding_tpu import checkpoint as ckpt
from openembedding_tpu import checkpoint_delta as cd
from openembedding_tpu.analysis import chaos
from openembedding_tpu.analysis.concurrency import (clear_schedule,
                                                    install_schedule)
from openembedding_tpu.dirty import (DirtyTracker, RowTracker,
                                     make_array_tracker)
from openembedding_tpu.parallel.mesh import create_mesh

FEATURES = ("c0", "c1", "c2")
VOCAB, DIM, B = 4096, 4, 16


def _batches(n, seed=0):
    from openembedding_tpu.models import deepctr
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        sparse = {}
        # a narrow id range: later steps re-touch earlier steps' rows (one
        # draw for every feature, so every table has the same dirty rows)
        ids = rng.randint(0, 64, size=B).astype(np.int32)
        for f in FEATURES:
            sparse[f] = ids
            sparse[f + deepctr.LINEAR_SUFFIX] = ids
        out.append({"label": (sparse["c0"] % 2).astype(np.float32),
                    "dense": rng.randn(B, 4).astype(np.float32),
                    "sparse": sparse})
    return out


def _trainer(mesh, **tracking):
    import optax
    from openembedding_tpu import EmbeddingCollection, Trainer
    from openembedding_tpu.models import deepctr
    coll = EmbeddingCollection(
        deepctr.make_feature_specs(FEATURES, VOCAB, DIM), mesh,
        default_optimizer={"category": "adagrad", "learning_rate": 0.1})
    coll.enable_dirty_tracking(**tracking)
    return Trainer(deepctr.build_model("deepfm", FEATURES), coll,
                   optax.adam(1e-2))


def _start(mesh, batches, **tracking):
    tr = _trainer(mesh, **tracking)
    return tr, tr.init(jax.random.PRNGKey(0), tr.shard_batch(batches[0]))


def _live(tr, emb):
    """``live(vid, field, lo, hi)`` of a 1x1 mesh: logical = physical."""
    names = {tr.collection.variable_id(n): n for n in tr.collection.specs}

    def live(vid, field, lo, hi):
        state = emb[names[vid]]
        array = state.weights if field == "weights" \
            else state.slots[field[len("slot_"):]]
        return np.asarray(array[lo:hi])
    return live


def _distinct(batches):
    return len(set(np.concatenate([b["sparse"]["c0"] for b in batches])))


@pytest.fixture(scope="module")
def mesh(devices8):
    return create_mesh(1, 1, devices8[:1])


class HoldWriter:
    """A schedule that parks the autosave's writer at ``ckpt.delta.write``
    until the step loop has reached ``trainer.fit.step`` ``until`` times:
    the save is written while later steps have already run."""

    def __init__(self, until):
        self.until, self.steps = until, 0
        self.released = threading.Event()
        self.wrote_after = None

    def sync(self, key, point):
        if point == "trainer.fit.step":
            self.steps += 1
            if self.steps >= self.until:
                self.released.set()
        elif point == "ckpt.delta.write" and self.wrote_after is None:
            assert self.released.wait(60), "the step loop never got there"
            self.wrote_after = self.steps


# --- the dirty set ------------------------------------------------------------

def test_row_tracker_hands_out_the_rows_marked_in_arrival_order():
    t = make_array_tracker("t", 1 << 20)
    assert isinstance(t, RowTracker) and t.rows_per_chunk == 1
    t.mark_rows([9, 3, 9, 1 << 19])
    t.mark_rows([3, 7])
    assert t.dirty_count == 4
    snap = t.snapshot_clear()
    assert list(snap) == [3, 9, 1 << 19, 7]      # sorted within a mark
    assert t.dirty_count == 0 and not t.dirty_chunks().size
    t.mark_rows([5])                              # landed during the write
    t.restore(snap)
    assert sorted(t.snapshot_clear()) == [3, 5, 7, 9, 1 << 19]
    # what drops the log falls back to a scan, then logs again
    t.mark_rows([11, 2])
    t.clear_chunks([11])
    assert list(t.snapshot_clear()) == [2]
    t.mark_all()
    assert t.snapshot_clear().size == 1 << 20
    t.mark_rows([8, 4])
    assert list(t.snapshot_clear()) == [4, 8]
    # the log grows past its first buffer
    many = np.random.RandomState(0).permutation(1 << 20)[:200_000]
    t.mark_rows(many[:100_000])
    t.mark_rows(many[100_000:])
    assert set(t.snapshot_clear()) == set(many)


def test_target_chunks_keeps_chunks_of_contiguous_rows():
    t = make_array_tracker("t", 1000, target_chunks=8)
    assert type(t) is DirtyTracker
    assert (t.num_chunks, t.rows_per_chunk) == (8, 125)
    t.mark_rows([0, 999])
    assert list(t.snapshot_clear()) == [0, 7]


@pytest.mark.parametrize("shape", [(1, 1), (2, 4)])
def test_snapshot_program_reads_the_rows_at_their_ids(devices8, shape):
    from openembedding_tpu import EmbeddingCollection, EmbeddingSpec
    from openembedding_tpu.parallel import sharded_table as st
    mesh = create_mesh(*shape, devices8[:shape[0] * shape[1]])
    coll = EmbeddingCollection(
        (EmbeddingSpec(name="a", input_dim=10_000, output_dim=9),), mesh,
        default_optimizer={"category": "adagrad", "learning_rate": 0.1})
    state = coll.init(jax.random.PRNGKey(0))["a"]
    spec = coll.sharding_spec("a")
    ids = np.random.RandomState(1).permutation(10_000)[:5_000]
    shard, local = spec.shard_and_local(ids)
    phys = np.full(8192, -1, np.int32)
    phys[:ids.size] = shard * spec.rows_per_shard + local
    rows, accum = st.snapshot_rows_sharded(
        [state.weights, state.slots["accum"]], jnp.asarray(phys), ids.size,
        mesh=mesh, spec=spec)
    want = np.asarray(coll.pull({"a": state},
                                {"a": jnp.asarray(ids.astype(np.int32))},
                                batch_sharded=False)["a"])
    np.testing.assert_array_equal(np.asarray(rows)[:ids.size], want)
    assert not np.asarray(rows)[ids.size:].any()      # beyond count: zeros
    assert (np.asarray(accum)[:ids.size] > 0).all()


# --- a save in flight while later steps re-touch its rows ---------------------

def test_entry_holds_the_table_at_its_step_not_what_the_rows_became(
        mesh, tmp_path):
    batches = _batches(6)
    path = str(tmp_path / "auto")
    tr, state = _start(mesh, batches)
    ckpt.save_checkpoint(path, tr.collection, state.emb,
                         dense_state=(state.params, state.opt_state),
                         mode="delta")                    # the base
    hold = HoldWriter(until=6)
    install_schedule(hold)
    try:
        state, _ = tr.fit(state, list(batches), autosave_every=3,
                          autosave_dir=path)
    finally:
        clear_schedule()
    assert hold.wrote_after >= 6    # written once steps 4 and 5 had run
    manifest = cd.read_manifest(path)
    assert manifest["format"] == 2      # array rows: block checksums
    assert [e["step"] for e in manifest["chain"]] == [3, 6]
    assert [e["extra"]["fit"]["cursor"] for e in manifest["chain"]] == [3, 6]

    # an entry's rows are the distinct ids pushed since the snapshot before
    want_rows = [_distinct(batches[:3]), _distinct(batches[3:])]
    for held, want in zip(reference_chain.entry_rows(path), want_rows):
        assert set(held.values()) == {want}
    for entry in manifest["chain"]:
        for record in entry["vars"].values():
            assert record["block_rows"] == cd.CRC_BLOCK_ROWS
            assert len(record["block_crc"]) == 1 and "chunk_crc" not in record

    # the first entry against a second trainer stopped at its step
    tr3, s3 = _start(mesh, batches)
    s3, _ = tr3.fit(s3, list(batches[:3]))
    assert reference_chain.mismatch_rows(path, _live(tr3, s3.emb),
                                         entries=1) == 0
    assert reference_chain.mismatch_rows(path, _live(tr, state.emb),
                                         entries=1) > 0    # rows moved on
    # the whole chain: the reference, the live table, the program's replay
    assert reference_chain.mismatch_rows(path, _live(tr, state.emb)) == 0
    tr2, s2 = _start(mesh, batches)
    loaded = ckpt.load_checkpoint(path, tr2.collection)
    assert reference_chain.mismatch_rows(path, _live(tr2, loaded)) == 0


def test_fit_returns_after_the_last_commit_and_one_save_is_in_flight(
        mesh, tmp_path):
    batches = _batches(8, seed=1)
    path = str(tmp_path / "auto")
    tr, state = _start(mesh, batches)
    ckpt.save_checkpoint(path, tr.collection, state.emb, mode="delta")
    alive = []
    real = cd.begin_delta

    def counted(*args, **kw):
        alive.append(sum(t.name == "oe-ckpt-autosave"
                         for t in threading.enumerate()))
        return real(*args, **kw)

    cd.begin_delta = counted
    try:
        state, _ = tr.fit(state, list(batches), autosave_every=2,
                          autosave_dir=path)
    finally:
        cd.begin_delta = real
    assert alive == [0, 0, 0, 0]     # each snapshot waited for the save before
    assert not any(t.name == "oe-ckpt-autosave"
                   for t in threading.enumerate())
    assert cd.chain_state(path)["last_seq"] == 4
    assert reference_chain.mismatch_rows(path, _live(tr, state.emb)) == 0


# --- kills and failed writes --------------------------------------------------

def test_kill_before_the_commit_leaves_the_previous_chain(mesh, tmp_path):
    """The writer dies between the snapshot and the manifest rename of the
    second save: the directory is the chain as the first save left it."""
    batches = _batches(6, seed=2)
    path = str(tmp_path / "auto")
    tr, state = _start(mesh, batches)
    ckpt.save_checkpoint(path, tr.collection, state.emb,
                         dense_state=(state.params, state.opt_state),
                         mode="delta")
    plan = chaos.FaultPlan([chaos.FaultSpec(
        point="ckpt.delta.commit", action="kill_thread", hit=2)])
    with chaos.active_plan(plan):
        with pytest.raises(RuntimeError, match="autosave failed") as err:
            tr.fit(state, list(batches), autosave_every=2,
                   autosave_dir=path)
    assert plan.injected and isinstance(err.value.__cause__, chaos.ChaosKill)
    assert [e["step"] for e in cd.read_manifest(path)["chain"]] == [2]
    tr2, s2 = _start(mesh, batches)
    s2, _ = tr2.fit(s2, list(batches[:2]))
    assert reference_chain.mismatch_rows(path, _live(tr2, s2.emb)) == 0
    # the rows of the save that died are dirty again
    assert tr.collection.dirty_trackers["c0"].dirty_count \
        >= _distinct(batches[2:4])


def test_kill_after_the_commit_leaves_the_new_chain(mesh, tmp_path):
    """The step loop dies one step after a save was dispatched: ``fit``
    unwinds through the writer's join, so that save is committed."""
    batches = _batches(6, seed=3)
    path = str(tmp_path / "auto")
    tr, state = _start(mesh, batches)
    ckpt.save_checkpoint(path, tr.collection, state.emb, mode="delta")
    plan = chaos.FaultPlan([chaos.FaultSpec(
        point="trainer.fit.step", action="kill_thread", hit=5)])
    with chaos.active_plan(plan):
        with pytest.raises(chaos.ChaosKill):
            tr.fit(state, list(batches), autosave_every=2,
                   autosave_dir=path)
    manifest = cd.read_manifest(path)
    assert [e["extra"]["fit"]["cursor"] for e in manifest["chain"]] == [2, 4]
    tr2, s2 = _start(mesh, batches)
    s2, _ = tr2.fit(s2, list(batches[:4]))
    assert reference_chain.mismatch_rows(path, _live(tr2, s2.emb)) == 0


def test_failed_write_marks_its_rows_again(mesh, tmp_path):
    batches = _batches(2, seed=4)
    path = str(tmp_path / "auto")
    tr, state = _start(mesh, batches)
    ckpt.save_checkpoint(path, tr.collection, state.emb, mode="delta")
    plan = chaos.FaultPlan([chaos.FaultSpec(
        point="ckpt.delta.write", action="raise", hit=1)])
    with chaos.active_plan(plan):
        with pytest.raises(RuntimeError, match="autosave failed"):
            tr.fit(state, list(batches), autosave_every=2,
                   autosave_dir=path)
    assert not cd.read_manifest(path)["chain"]
    want = _distinct(batches)
    assert {t.dirty_count
            for t in tr.collection.dirty_trackers.values()} == {want}
    # the next save carries them (fit donated ``state``: train again)
    tr2, s2 = _start(mesh, batches)
    ckpt.save_checkpoint(path, tr2.collection, s2.emb, mode="delta")
    s2, _ = tr2.fit(s2, list(batches))
    info = ckpt.save_checkpoint(path, tr2.collection, s2.emb, mode="delta",
                                step=2)
    assert info["rows"] == want * len(tr2.collection.specs)
    assert reference_chain.mismatch_rows(path, _live(tr2, s2.emb)) == 0


# --- formats ------------------------------------------------------------------

def test_chunked_chains_stay_format_1_and_both_formats_load(mesh, tmp_path):
    batches = _batches(4, seed=5)
    for tracking, fmt, crc in (({"target_chunks": 8}, 1, "chunk_crc"),
                               ({}, 2, "block_crc")):
        path = str(tmp_path / f"format{fmt}")
        tr, state = _start(mesh, batches, **tracking)
        ckpt.save_checkpoint(path, tr.collection, state.emb, mode="delta")
        assert cd.read_manifest(path)["format"] == 1      # a bare base
        state, _ = tr.fit(state, list(batches), autosave_every=2,
                          autosave_dir=path)
        manifest = cd.read_manifest(path)
        assert manifest["format"] == fmt
        records = [r for e in manifest["chain"] for r in e["vars"].values()]
        assert records and all(crc in r for r in records)
        verified, dropped = cd.verify_chain(path, manifest)
        assert len(verified) == 2 and not dropped
        tr2, _ = _start(mesh, batches, **tracking)
        loaded = ckpt.load_checkpoint(path, tr2.collection)
        assert reference_chain.mismatch_rows(path, _live(tr2, loaded)) == 0
        assert reference_chain.mismatch_rows(path, _live(tr, state.emb)) == 0
        # a flipped block checksum is a torn tail, as a chunk's is
        record = manifest["chain"][-1]["vars"]["c0"]
        record[crc][0] ^= 1
        cd._write_manifest(path, manifest)
        with pytest.warns(RuntimeWarning, match="torn"):
            verified, dropped = cd.verify_chain(path,
                                                cd.read_manifest(path))
        assert len(verified) == 1 and dropped


def test_native_reader_takes_a_row_exact_chain(mesh, tmp_path):
    from openembedding_tpu.serving import native
    from openembedding_tpu.serving.native import NativeModel
    native_lib = native.build_library()
    batches = _batches(4, seed=6)
    path = str(tmp_path / "native")
    tr, state = _start(mesh, batches)
    ckpt.save_checkpoint(path, tr.collection, state.emb, mode="delta",
                         model_sign="rows")
    state, _ = tr.fit(state, list(batches), autosave_every=2,
                      autosave_dir=path)
    ids = np.arange(128)
    want = np.asarray(tr.collection.pull(
        state.emb, {"c0": jnp.asarray(ids.astype(np.int32))},
        batch_sharded=False, read_only=True)["c0"], np.float32)
    with NativeModel(path, native_lib) as m:
        assert m.version == 2
        np.testing.assert_array_equal(
            m.lookup("c0", ids).astype(np.float32), want)
    manifest = cd.read_manifest(path)
    manifest["chain"][-1]["vars"]["c0"]["block_crc"][0] ^= 1
    cd._write_manifest(path, manifest)
    with NativeModel(path, native_lib) as m:      # torn tail: one back
        assert m.version == 1


# --- hash tables: a dirty set exact to the key --------------------------------

from benchmark import reference_chain_keys  # noqa: E402
from openembedding_tpu import hash_table as hash_lib  # noqa: E402
from openembedding_tpu.dirty import KeyTracker, make_hash_tracker  # noqa: E402

HASH_CAPACITY = 8192
KEYED = [("int32", (1, 1)), ("wide", (1, 1)), ("int32", (1, 2)),
         ("wide", (1, 2))]
KEYED_IDS = [f"{k}-{d}x{m}" for k, (d, m) in KEYED]


def _hash_trainer(devices, key_dtype, shape):
    import optax
    from openembedding_tpu import EmbeddingCollection, Trainer
    from openembedding_tpu.fused import make_fused_specs
    from openembedding_tpu.models import deepctr
    mesh = create_mesh(*shape, devices[:shape[0] * shape[1]])
    specs, mapper = make_fused_specs(
        FEATURES, -1, DIM,
        optimizer={"category": "adagrad", "learning_rate": 0.1},
        hash_capacity=HASH_CAPACITY, key_dtype=key_dtype)
    coll = EmbeddingCollection(specs, mesh)
    coll.enable_dirty_tracking()
    return Trainer(deepctr.build_model("deepfm", FEATURES), coll,
                   optax.adam(1e-2)), mapper


def _hash_batches(mapper, n, key_dtype, seed=0, batch=B):
    """Batches whose id range grows with the step, so that every period
    re-touches earlier keys and inserts keys no step before it pushed."""
    rng = np.random.RandomState(seed)
    dtype = np.int32 if key_dtype == "int32" else np.int64
    scale = 1 if key_dtype == "int32" else 10 ** 9 + 7
    out = []
    for t in range(n):
        ids = rng.randint(0, 24 + 8 * t, size=batch).astype(dtype) * scale
        out.append(mapper.fuse_batch({
            "label": (ids % 2).astype(np.float32),
            "dense": rng.randn(batch, 4).astype(np.float32),
            "sparse": {f: ids for f in FEATURES}}))
    return out


def _prefilled(tr, mapper, batches, key_dtype):
    """A state whose tables hold a thousand keys a feature before anything
    is saved: the base then stays well above twice the chain, and the
    compactor leaves the entries these tests read on disk."""
    dtype = np.int32 if key_dtype == "int32" else np.int64
    ids = (np.arange(1024) + 5000).astype(dtype)
    fill = mapper.fuse_batch({
        "label": np.zeros(1024, np.float32),
        "dense": np.zeros((1024, 4), np.float32),
        "sparse": {f: ids for f in FEATURES}})
    state = tr.init(jax.random.PRNGKey(0), tr.shard_batch(batches[0]))
    return tr.fit(state, [fill])[0]


def _keys_of(batches, name):
    """Distinct 64-bit keys a run of fused batches pushes to ``name``."""
    keys = np.concatenate([np.asarray(b["sparse"][name]) for b in batches])
    keys = hash_lib.join64(keys.reshape(-1, 2)) if keys.shape[-1] == 2 \
        and keys.ndim == 3 else keys.astype(np.int64).ravel()
    return np.unique(keys)


def _held(state):
    """(keys sorted, weights, {slot: rows}) of a hash table's live keys."""
    keys = np.asarray(state.keys)
    live = reference_chain_keys.is_live(keys)
    k64 = reference_chain_keys.keys64(keys[live])
    order = np.argsort(k64)
    return (k64[order], np.asarray(state.weights)[live][order],
            {s: np.asarray(v)[live][order] for s, v in state.slots.items()})


def _assert_same_table(a, b):
    ka, wa, sa = _held(a)
    kb, wb, sb = _held(b)
    np.testing.assert_array_equal(ka, kb)
    np.testing.assert_array_equal(wa.view(np.uint32), wb.view(np.uint32))
    for s in sa:
        np.testing.assert_array_equal(sa[s].view(np.uint32),
                                      sb[s].view(np.uint32))


def _hash_live(tr, emb):
    names = {tr.collection.variable_id(n): n for n in tr.collection.specs}

    def live(vid, field, lo, hi):
        state = emb[names[vid]]
        array = state.keys if field == "keys" \
            else state.weights if field == "weights" \
            else state.slots[field[len("slot_"):]]
        return np.asarray(array)[lo:hi]
    return live


def _chain_faults(tr, path, emb):
    cd.join_compactor(path)     # a tiny base: the last save may fold
    found = reference_chain_keys.compare(
        path, _hash_live(tr, emb),
        next(iter(emb.values())).keys.shape[0])
    return {k: found[k] for k in ("mismatch_rows", "missing_keys",
                                  "extra_keys")}, found["new_keys"]


NO_FAULT = {"mismatch_rows": 0, "missing_keys": 0, "extra_keys": 0}


def test_key_tracker_is_exact_to_the_key_and_keeps_arrival_order():
    t = make_hash_tracker("h", 1 << 20)
    assert isinstance(t, KeyTracker)
    assert isinstance(make_hash_tracker("h", 1 << 20, 64), DirtyTracker)
    a = np.array([5, 5 + 1024, -7, 1 << 61, 5], np.int64)
    t.mark_keys(a)                       # key % 1024 would fold two of them
    t.mark_keys(np.array([9, 5], np.int64))
    assert t.dirty_count == 5
    snap = t.snapshot_clear()
    assert sorted(snap[:4]) == sorted({5, 5 + 1024, -7, 1 << 61})
    assert snap[4] == 9 and t.dirty_count == 0
    t.mark_keys(np.array([9], np.int64))
    t.restore(snap)                      # a failed writer's claim comes back
    assert sorted(t.snapshot_clear()) == sorted(set(snap.tolist()))
    # the set grows past its first table and stays exact
    rng = np.random.RandomState(1)
    many = rng.randint(-2 ** 62, 2 ** 62, size=300_000).astype(np.int64)
    for part in np.array_split(many, 7):
        t.mark_keys(part)
    assert t.dirty_count == np.unique(many).size
    assert np.array_equal(np.sort(t.dirty_keys()), np.unique(many))
    assert t.nbytes > 0 and t.snapshot_clear().size == t.dirty_count + \
        np.unique(many).size and not t.snapshot_clear().size


@pytest.mark.parametrize("key_dtype,shape", KEYED, ids=KEYED_IDS)
def test_hash_chain_restores_the_live_table_key_for_key(
        devices8, tmp_path, key_dtype, shape):
    """N steps with K saves through ``fit``: base and chain restore, into
    a fresh collection, exactly the live table's keys, weights and
    accumulators, the keys inserted between saves among them; each entry
    holds exactly the distinct keys pushed since the entry before."""
    tr, mapper = _hash_trainer(devices8, key_dtype, shape)
    batches = _hash_batches(mapper, 9, key_dtype)
    state = _prefilled(tr, mapper, batches, key_dtype)
    path = str(tmp_path / "auto")
    state, _ = tr.fit(state, batches[:3])
    info = ckpt.save_checkpoint(path, tr.collection, state.emb,
                                mode="delta")
    assert info["forced_full"]
    state, _ = tr.fit(state, batches[3:], autosave_every=2,
                      autosave_dir=path)
    manifest = cd.read_manifest(path)
    assert manifest["format"] == cd.DELTA_FORMAT == 3
    assert [e["extra"]["fit"]["cursor"]
            for e in manifest["chain"]] == [2, 4, 6]
    held = reference_chain_keys.entry_keys(path)
    for i, entry in enumerate(manifest["chain"]):
        period = batches[3 + 2 * i:5 + 2 * i]
        for name, record in entry["vars"].items():
            want = _keys_of(period, name)
            assert record["keys_exact"] and record["rows"] == want.size
            assert "dirty_chunks" not in record
            payload = cd._entry_payload(path, entry, name)
            assert sorted(payload) == ["keys", "slot_accum", "weights"]
            assert np.array_equal(np.sort(
                reference_chain_keys.keys64(payload["keys"])), want)
            assert held[i][tr.collection.variable_id(name)] == want.size
    tr2, _ = _hash_trainer(devices8, key_dtype, shape)
    loaded = ckpt.load_checkpoint(path, tr2.collection)
    for name in tr.collection.specs:
        _assert_same_table(loaded[name], state.emb[name])
        assert int(loaded[name].insert_failures) == 0
    # the plain reference agrees, and every entry brought new keys
    faults, new_keys = _chain_faults(tr, path, state.emb)
    assert faults == NO_FAULT
    assert all(n > 0 for per_var in new_keys.values() for n in per_var)
    assert _chain_faults(tr2, path, loaded)[0] == NO_FAULT


@pytest.mark.parametrize("key_dtype,shape", KEYED[1::2], ids=KEYED_IDS[1::2])
def test_entry_of_a_hash_table_holds_it_at_its_step(devices8, tmp_path,
                                                    key_dtype, shape):
    """Written while later steps push the same keys again and insert
    others, an entry equals the table at the entry's own step."""
    tr, mapper = _hash_trainer(devices8, key_dtype, shape)
    batches = _hash_batches(mapper, 6, key_dtype, seed=2)
    state = _prefilled(tr, mapper, batches, key_dtype)
    path = str(tmp_path / "auto")
    ckpt.save_checkpoint(path, tr.collection, state.emb, mode="delta")
    hold = HoldWriter(until=6)
    install_schedule(hold)
    try:
        state, _ = tr.fit(state, list(batches), autosave_every=3,
                          autosave_dir=path)
    finally:
        clear_schedule()
    assert hold.wrote_after >= 6
    tr2, mapper2 = _hash_trainer(devices8, key_dtype, shape)
    s2 = _prefilled(tr2, mapper2, batches, key_dtype)
    s2, _ = tr2.fit(s2, list(batches[:3]))
    found = reference_chain_keys.compare(
        path, _hash_live(tr2, s2.emb),
        next(iter(s2.emb.values())).keys.shape[0], entries=1)
    assert {k: found[k] for k in NO_FAULT} == NO_FAULT


@pytest.mark.parametrize("key_dtype", ["int32", "wide"])
def test_marked_key_the_table_lacks_is_skipped_and_counted(
        devices8, tmp_path, key_dtype):
    from openembedding_tpu.utils import observability as obs
    tr, mapper = _hash_trainer(devices8, key_dtype, (1, 1))
    batches = _hash_batches(mapper, 3, key_dtype, seed=3)
    state = tr.init(jax.random.PRNGKey(0), tr.shard_batch(batches[0]))
    path = str(tmp_path / "m")
    state, _ = tr.fit(state, batches[:1])
    ckpt.save_checkpoint(path, tr.collection, state.emb, mode="delta")
    state, _ = tr.fit(state, batches[1:2])
    tr.collection.mark_dirty(batches[2]["sparse"])  # marked, never pushed
    absent = {n: np.setdiff1d(_keys_of(batches[2:], n),
                              _keys_of(batches[:2], n)).size
              for n in tr.collection.specs}
    assert all(absent.values())
    before = obs.GLOBAL.snapshot().get("ckpt_delta_keys_absent",
                                       {}).get("count", 0.0)
    info = cd.save_delta(path, tr.collection, state.emb, step=2,
                         return_payload=True, background_compact=False,
                         compact_bytes_ratio=1e18)
    after = obs.GLOBAL.snapshot()["ckpt_delta_keys_absent"]["count"]
    assert after - before == sum(absent.values())
    for name, payload in info["delta"].vars.items():
        want = np.intersect1d(_keys_of(batches[1:], name),
                              _keys_of(batches[:2], name))
        assert np.array_equal(np.sort(reference_chain_keys.keys64(
            payload["keys"])), want)
    assert _chain_faults(tr, path, state.emb)[0] == NO_FAULT
    # nothing was inserted by the snapshot's find
    assert int(state.emb[mapper.name].num_used()) \
        == _keys_of(batches[:2], mapper.name).size


def test_failed_write_puts_a_hash_tables_keys_back(devices8, tmp_path):
    tr, mapper = _hash_trainer(devices8, "wide", (1, 1))
    batches = _hash_batches(mapper, 2, "wide", seed=4)
    state = tr.init(jax.random.PRNGKey(0), tr.shard_batch(batches[0]))
    path = str(tmp_path / "auto")
    ckpt.save_checkpoint(path, tr.collection, state.emb, mode="delta")
    plan = chaos.FaultPlan([chaos.FaultSpec(
        point="ckpt.delta.write", action="raise", hit=1)])
    with chaos.active_plan(plan):
        with pytest.raises(RuntimeError, match="autosave failed"):
            tr.fit(state, list(batches), autosave_every=2,
                   autosave_dir=path)
    assert not cd.read_manifest(path)["chain"]
    want = _keys_of(batches, mapper.name)
    for tracker in tr.collection.dirty_trackers.values():
        assert np.array_equal(np.sort(tracker.dirty_keys()), want)
    tr2, _ = _hash_trainer(devices8, "wide", (1, 1))
    s2 = tr2.init(jax.random.PRNGKey(0), tr2.shard_batch(batches[0]))
    ckpt.save_checkpoint(path, tr2.collection, s2.emb, mode="delta")
    s2, _ = tr2.fit(s2, list(batches))
    info = ckpt.save_checkpoint(path, tr2.collection, s2.emb, mode="delta",
                                step=2)
    assert info["rows"] == want.size * len(tr2.collection.specs)
    assert _chain_faults(tr2, path, s2.emb)[0] == NO_FAULT


def test_format_2_chain_with_chunked_hash_records_still_loads(devices8,
                                                              tmp_path):
    """What PR 32's build wrote for a collection of both kinds: array
    rows to the row (``block_crc``: format 2), hash keys in ``key % n``
    chunks (``chunks`` / ``num_chunks`` members)."""
    from test_delta_checkpoint import assert_states_equal, make_coll, train
    mesh = create_mesh(2, 4, devices8)
    coll = make_coll(mesh, track=False)
    coll.enable_dirty_tracking(names={"arr"})
    coll.enable_dirty_tracking(target_chunks=16, names={"hsh"})
    states = coll.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "m")
    ckpt.save_checkpoint(path, coll, states, mode="delta", step=0)
    probes = []
    for seed in (1, 2):
        states, idx = train(coll, states, seed)
        probes.append(np.asarray(idx["hsh"]))
        cd.save_delta(path, coll, states, step=seed,
                      background_compact=False, compact_bytes_ratio=1e18)
    manifest = cd.read_manifest(path)
    assert manifest["format"] == 2
    record = manifest["chain"][-1]["vars"]["hsh"]
    assert "dirty_chunks" in record and "keys_exact" not in record
    payload = cd._entry_payload(path, manifest["chain"][-1], "hsh")
    assert {"chunks", "num_chunks", "keys"} <= set(payload)
    coll2 = make_coll(mesh, track=False)
    loaded = ckpt.load_checkpoint(path, coll2)
    assert_states_equal(coll, states, loaded,
                        probe_keys=np.concatenate(probes))
    # the same directory takes an entry exact to the key next
    coll3 = make_coll(mesh, track=False)
    coll3.enable_dirty_tracking()
    states3 = ckpt.load_checkpoint(path, coll3)
    states3, idx = train(coll3, states3, 3)
    info = cd.save_delta(path, coll3, states3, step=3,
                         background_compact=False, compact_bytes_ratio=1e18)
    assert not info["skipped"]
    assert cd.read_manifest(path)["format"] == 3
    coll4 = make_coll(mesh, track=False)
    assert_states_equal(coll3, states3, ckpt.load_checkpoint(path, coll4),
                        probe_keys=np.concatenate(
                            probes + [np.asarray(idx["hsh"])]))


@pytest.mark.parametrize("compacted", [False, True],
                         ids=["chain", "folded"])
def test_serving_and_the_native_reader_take_keyed_entries(
        devices8, tmp_path, compacted):
    """``ModelRegistry.apply_delta``, ``read_delta`` / ``encode_delta``,
    the compactor's fold and ``native/oe_serving.cc`` read entries that
    are exact to the key, new keys among them."""
    from test_delta_checkpoint import make_coll, train
    from openembedding_tpu.serving.registry import ModelRegistry
    mesh = create_mesh(2, 4, devices8)
    coll = make_coll(mesh, track=False)
    coll.enable_dirty_tracking()
    states = coll.init(jax.random.PRNGKey(0))
    path = str(tmp_path / "m")
    states, idx0 = train(coll, states, 0)
    ckpt.save_checkpoint(path, coll, states, mode="delta", step=0,
                         model_sign="keyed")
    reg = ModelRegistry(mesh, default_hash_capacity=2048)
    sign = reg.create_model(path, block=True)
    model = reg.find_model(sign)
    probes = [np.asarray(idx0["hsh"])]
    for seed in (1, 2):
        states, idx = train(coll, states, seed)
        probes.append(np.asarray(idx["hsh"]))
        info = cd.save_delta(path, coll, states, step=seed,
                             return_payload=True, background_compact=False,
                             compact_bytes_ratio=1e18)
        assert "chunks" not in info["delta"].vars["hsh"]
        wire = cd.decode_delta(cd.encode_delta(cd.read_delta(path)))
        assert np.array_equal(wire.vars["hsh"]["keys"],
                              info["delta"].vars["hsh"]["keys"])
        out = reg.apply_delta(sign, wire)
        assert out["applied"] and model.version == seed
    keys = np.concatenate(probes)
    want = np.asarray(coll.pull(states, {"hsh": jnp.asarray(keys)},
                                batch_sharded=False,
                                read_only=True)["hsh"])
    np.testing.assert_array_equal(
        want, np.asarray(model.lookup("hsh", keys)))
    if compacted:
        assert cd.compact(path)["compacted"]
        assert not cd.read_manifest(path)["chain"]
    coll2 = make_coll(mesh, track=False)
    loaded = ckpt.load_checkpoint(path, coll2)
    np.testing.assert_array_equal(want, np.asarray(coll2.pull(
        loaded, {"hsh": jnp.asarray(keys)}, batch_sharded=False,
        read_only=True)["hsh"]))
    from openembedding_tpu.serving import native
    lib = native.build_library()
    if lib is None:
        pytest.skip("no C++ toolchain")
    with native.NativeModel(path, lib) as m:
        assert m.version == 2
        np.testing.assert_array_equal(
            m.lookup("hsh", keys.astype(np.int64)).astype(np.float32),
            want.astype(np.float32))

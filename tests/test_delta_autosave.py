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
    assert manifest["format"] == cd.DELTA_FORMAT
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
                               ({}, cd.DELTA_FORMAT, "block_crc")):
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

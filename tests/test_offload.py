"""Host-offload tier: cache residency, writeback, incremental persist/restore
— the reference's PMem test matrix (pmem_embedding_table_test.cpp: set/get
across work_ids, checkpoint commit, cache eviction with tiny budgets,
load_pmem_pool recovery; pmem_c_api_test.cpp: train/persist/restore loop)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu import EmbeddingVariableMeta, make_optimizer
from openembedding_tpu.offload import HostOffloadedTable

DIM = 4
META = EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=1000)


def make_table(**kw):
    kw.setdefault("vocab", 1000)
    kw.setdefault("cache_capacity", 256)
    return HostOffloadedTable(
        META, {"category": "sgd", "learning_rate": 1.0},
        {"category": "constant", "value": 0.5}, **kw)


def test_pull_through_cache_matches_host():
    t = make_table()
    ids = np.array([1, 500, 999], np.int32)
    t.prepare(ids)
    rows = np.asarray(t.pull(jnp.asarray(ids)))
    np.testing.assert_allclose(rows, t.host_weights[ids], rtol=1e-6)


def test_update_flush_writeback():
    t = make_table()
    ids = np.array([7, 8, 9], np.int32)
    t.prepare(ids)
    t.apply_gradients(jnp.asarray(ids), jnp.ones((3, DIM), jnp.float32))
    # host copy still stale until flush
    np.testing.assert_allclose(t.host_weights[ids], 0.5)
    flushed = t.flush()
    assert flushed == 3
    np.testing.assert_allclose(t.host_weights[ids], 0.5 - 1.0, rtol=1e-6)
    assert (t.host_work_id[ids] > 0).all()
    # state round-trips: rows come back with their values after re-prepare
    t.prepare(ids)
    np.testing.assert_allclose(np.asarray(t.pull(jnp.asarray(ids))),
                               0.5 - 1.0, rtol=1e-6)


def test_tiny_cache_eviction_cycle():
    """Cache smaller than the id stream: prepare must flush-and-refill, and
    values stay exact across evictions (the 1-5 item cache-budget tests)."""
    t = make_table(cache_capacity=64)
    rng = np.random.RandomState(0)
    host_replica = t.host_weights.copy()
    for step in range(8):
        ids = rng.randint(0, 1000, 40).astype(np.int32)
        uniq = np.unique(ids)
        t.prepare(ids)
        t.apply_gradients(jnp.asarray(uniq),
                          jnp.ones((uniq.size, DIM), jnp.float32) * 0.1)
        host_replica[uniq] -= 0.1
    t.flush()
    np.testing.assert_allclose(t.host_weights, host_replica, rtol=1e-5,
                               atol=1e-6)


def test_incremental_persist_restore(tmp_path):
    t = make_table()
    p = str(tmp_path / "off")
    ids1 = np.array([1, 2, 3], np.int32)
    t.prepare(ids1)
    t.apply_gradients(jnp.asarray(ids1), jnp.ones((3, DIM), jnp.float32))
    info = t.persist(p)
    assert info["file"].startswith("base_")

    ids2 = np.array([10, 11], np.int32)
    t.prepare(ids2)
    t.apply_gradients(jnp.asarray(ids2),
                      jnp.ones((2, DIM), jnp.float32) * 2.0)
    info2 = t.persist(p)
    assert info2["file"].startswith("inc_")
    assert info2["rows"] == 2  # only the changed rows hit disk

    # fresh process restores base + increment
    t2 = make_table()
    t2.restore(p)
    np.testing.assert_allclose(t2.host_weights[ids1], 0.5 - 1.0, rtol=1e-6)
    np.testing.assert_allclose(t2.host_weights[ids2], 0.5 - 2.0, rtol=1e-6)
    np.testing.assert_allclose(t2.host_weights[20], 0.5)
    # optimizer state slots restored too
    assert set(t2.host_slots) == set(t.host_slots)
    # restore continues past the persisted watermark
    assert t2.work_id > t2.persisted_work


def test_persist_chain_compaction(tmp_path):
    """A long run's incremental chain rebases instead of growing forever.

    The reference's incremental-commit protocol periodically rebases
    (PmemEmbeddingTable.h:297-328); without it the file count, meta size,
    and restore replay time grow unboundedly.
    """
    import os
    from openembedding_tpu import offload as off
    t = make_table()
    p = str(tmp_path / "off")
    for step in range(off.COMPACT_CHAIN_LEN + 3):
        ids = np.array([step % 16, 16 + step % 7], np.int32)
        t.prepare(ids)
        t.apply_gradients(jnp.asarray(ids),
                          jnp.ones((2, DIM), jnp.float32) * (step + 1))
        t.persist(p)
    import json
    with open(os.path.join(p, off.OFFLOAD_META_FILE)) as f:
        chain = json.load(f)["checkpoints"]
    assert len(chain) <= off.COMPACT_CHAIN_LEN
    # superseded files are deleted, listed files exist
    files = {e["file"] for e in chain}
    on_disk = {f for f in os.listdir(p) if f.endswith(".npz")}
    assert on_disk == files
    # restore parity with the uncompacted writer's state
    t2 = make_table()
    t2.restore(p)
    np.testing.assert_allclose(t2.host_weights, t.host_weights, rtol=1e-6)


def test_should_persist_window():
    t = make_table(persist_pending_window=3)
    ids = np.array([1], np.int32)
    assert not t.should_persist
    for _ in range(3):
        t.prepare(ids)
        t.apply_gradients(jnp.asarray(ids), jnp.ones((1, DIM), jnp.float32))
    assert t.should_persist


def test_restore_vocab_mismatch(tmp_path):
    t = make_table()
    p = str(tmp_path / "off")
    t.persist(p)
    t2 = HostOffloadedTable(
        EmbeddingVariableMeta(embedding_dim=DIM, vocabulary_size=500),
        {"category": "sgd", "learning_rate": 1.0}, vocab=500,
        cache_capacity=64)
    with pytest.raises(ValueError, match="vocab"):
        t2.restore(p)


# --- sharded offload tier ----------------------------------------------------

class TestShardedOffload:
    def _make(self, mesh, vocab=1024, cache=128, **kw):
        from openembedding_tpu.offload import ShardedOffloadedTable
        meta = EmbeddingVariableMeta(embedding_dim=4, vocabulary_size=vocab)
        return ShardedOffloadedTable(
            "off", meta, {"category": "adagrad", "learning_rate": 0.1},
            {"category": "constant", "value": 0.25},
            vocab=vocab, cache_capacity=cache, mesh=mesh, **kw)

    def _ground_truth_steps(self, batches):
        """Plain in-HBM array table trained on the same stream."""
        from openembedding_tpu import create_table, apply_gradients, pull
        meta = EmbeddingVariableMeta(embedding_dim=4, vocabulary_size=1024)
        opt = make_optimizer({"category": "adagrad", "learning_rate": 0.1})
        t = create_table(meta, opt,
                         {"category": "constant", "value": 0.25})
        for ids, grads in batches:
            t = apply_gradients(t, opt, jnp.asarray(ids), jnp.asarray(grads))
        return t

    def _stream(self, steps, seed=0):
        rng = np.random.RandomState(seed)
        out = []
        for i in range(steps):
            # rotate through id ranges so the small cache must evict
            lo = (i * 160) % 800
            ids = rng.randint(lo, lo + 200, 64).astype(np.int32)
            out.append((ids, rng.randn(64, 4).astype(np.float32)))
        return out

    def test_eviction_parity_with_plain_table(self, devices8):
        from openembedding_tpu.parallel.mesh import create_mesh
        from openembedding_tpu.parallel import sharded_hash as sh
        mesh = create_mesh(2, 4, devices8)
        table = self._make(mesh, cache=256)
        cache = table.create_cache()
        stream = self._stream(8)
        for ids, grads in stream:
            cache = table.prepare(cache, ids)
            rows = sh.pull_sharded(cache, jnp.asarray(ids), None,
                                   mesh=mesh, spec=table.spec,
                                   batch_sharded=False)
            cache = sh.apply_gradients_sharded(
                cache, table.optimizer, table.initializer,
                jnp.asarray(ids), jnp.asarray(grads),
                mesh=mesh, spec=table.spec, batch_sharded=False)
            table.note_update(ids)
        want = self._ground_truth_steps(stream)
        # flush everything and compare host store to ground truth
        table.flush(cache)
        table._join_writeback()
        from openembedding_tpu import pull
        probe = np.arange(1024, dtype=np.int32)
        np.testing.assert_allclose(
            table.host_weights, np.asarray(pull(want, jnp.asarray(probe))),
            rtol=1e-5, atol=1e-6)

    def test_persist_kill_restore_continue(self, devices8, tmp_path):
        """The reference's pmem_c_api_test.cpp:7-37 flow: train, persist,
        crash, restore, continue — equals an uninterrupted run."""
        from openembedding_tpu.parallel.mesh import create_mesh
        from openembedding_tpu.parallel import sharded_hash as sh
        mesh = create_mesh(2, 4, devices8)
        pdir = str(tmp_path / "persist")
        stream = self._stream(6, seed=3)

        def run(table, cache, items):
            for ids, grads in items:
                cache = table.prepare(cache, ids)
                cache = sh.apply_gradients_sharded(
                    cache, table.optimizer, table.initializer,
                    jnp.asarray(ids), jnp.asarray(grads),
                    mesh=mesh, spec=table.spec, batch_sharded=False)
                table.note_update(ids)
            return cache

        t1 = self._make(mesh, cache=256)
        c1 = run(t1, t1.create_cache(), stream[:3])
        t1.persist(c1, pdir)              # base checkpoint
        c1 = run(t1, c1, stream[3:])
        t1.persist(c1, pdir)              # incremental delta
        t1.flush(c1); t1._join_writeback()
        want = t1.host_weights.copy()

        # crash: a FRESH process-equivalent restores and replays nothing —
        # the persisted state must already be complete
        t2 = self._make(mesh, cache=256)
        c2 = t2.restore(pdir)
        np.testing.assert_allclose(t2.host_weights, want,
                                   rtol=1e-6, atol=1e-7)
        # restore resumes at the batch AFTER the persisted watermark
        assert t2.work_id == t1.work_id + 1
        assert t2.persisted_work == t1.persisted_work
        # continue training from the restored state: both runs agree
        more = self._stream(2, seed=9)
        c1 = run(t1, c1, more)
        c2 = run(t2, c2, more)
        t1.flush(c1); t1._join_writeback()
        t2.flush(c2); t2._join_writeback()
        np.testing.assert_allclose(t2.host_weights, t1.host_weights,
                                   rtol=1e-6, atol=1e-7)

    def test_trainer_integration(self, devices8):
        """Offloaded variable trains through Trainer.fit + eval path."""
        import optax
        from openembedding_tpu import EmbeddingCollection, Trainer
        from openembedding_tpu.models import deepctr
        from openembedding_tpu.parallel.mesh import create_mesh
        mesh = create_mesh(2, 4, devices8)
        table = self._make(mesh, vocab=4096, cache=256)
        spec = table.embedding_spec()
        lin = table.embedding_spec(name="off:linear", output_dim=1)
        coll = EmbeddingCollection((spec, lin), mesh)
        trainer = Trainer(
            deepctr.LogisticRegression(feature_names=("off",)),
            coll, optax.sgd(0.1), offload={"off": table})
        rng = np.random.RandomState(0)

        def batch():
            ids = rng.randint(0, 4096, 32).astype(np.int32)
            return {"label": (ids % 2).astype(np.float32), "dense": None,
                    "sparse": {"off": ids, "off:linear": ids}}

        state = trainer.init(jax.random.PRNGKey(0),
                             trainer.shard_batch(batch()))
        for _ in range(3):
            b = batch()
            state, m = trainer.train_step(state, b)
            assert np.isfinite(float(m["loss"]))
        assert table.work_id > 1
        b = batch()
        state = trainer.prepare_offload(state, b)
        scores = trainer.eval_step(state, b)
        assert scores.shape == (32,)


class TestPipelinedOffload:
    """The prepare-ahead pipeline (host gather of batch N+1 overlapping
    step N) and async persist must be bit-identical to the serial path —
    overlap is a scheduling change, not a numerics change (the reference's
    prefetch_pull_weights contract, exb_ops.cpp:109-205)."""

    def _trainer(self, mesh, vocab=2048, cache=256, depth=2):
        import optax
        from openembedding_tpu import EmbeddingCollection, Trainer
        from openembedding_tpu.models import deepctr
        from openembedding_tpu.offload import ShardedOffloadedTable
        meta = EmbeddingVariableMeta(embedding_dim=4, vocabulary_size=vocab)
        table = ShardedOffloadedTable(
            "off", meta, {"category": "adagrad", "learning_rate": 0.1},
            {"category": "constant", "value": 0.25},
            vocab=vocab, cache_capacity=cache, mesh=mesh,
            persist_pending_window=2)
        lin = ShardedOffloadedTable(
            "off:linear",
            EmbeddingVariableMeta(embedding_dim=1, vocabulary_size=vocab),
            {"category": "adagrad", "learning_rate": 0.1},
            {"category": "constant", "value": 0.25},
            vocab=vocab, cache_capacity=cache, mesh=mesh,
            persist_pending_window=2)
        coll = EmbeddingCollection(
            (table.embedding_spec(), lin.embedding_spec()), mesh)
        trainer = Trainer(
            deepctr.LogisticRegression(feature_names=("off",)),
            coll, optax.sgd(0.1),
            offload={"off": table, "off:linear": lin},
            pipeline_depth=depth)
        return trainer, table, lin

    def _batches(self, n, vocab=2048, seed=0):
        rng = np.random.RandomState(seed)
        out = []
        for i in range(n):
            lo = (i * 300) % (vocab - 400)
            ids = rng.randint(lo, lo + 400, 64).astype(np.int32)
            out.append({"label": (ids % 2).astype(np.float32),
                        "dense": None,
                        "sparse": {"off": ids, "off:linear": ids}})
        return out

    @pytest.mark.slow
    def test_packed_insert_matches_unpacked_fallback(self, devices8):
        """The one-transfer packed insert (keys bitcast into an f32
        column) must land bit-identical rows/slots to the generic
        per-array path — the fallback non-f32 tables take in production
        must not drift from the default path every f32 test exercises."""
        from openembedding_tpu.parallel.mesh import create_mesh
        mesh = create_mesh(2, 4, devices8)
        batches = self._batches(6)

        t_packed, tab_p, lin_p = self._trainer(mesh, cache=4096)
        assert tab_p._packed_layout(np.dtype(np.int32)) is not None
        s_p = t_packed.init(jax.random.PRNGKey(0),
                            t_packed.shard_batch(batches[0]))
        for b in batches:
            s_p, m_p = t_packed.train_step(s_p, b)

        t_plain, tab_u, lin_u = self._trainer(mesh, cache=4096)
        tab_u._packed_layout = lambda *_a, **_k: None   # force fallback
        lin_u._packed_layout = lambda *_a, **_k: None
        s_u = t_plain.init(jax.random.PRNGKey(0),
                           t_plain.shard_batch(batches[0]))
        for b in batches:
            s_u, m_u = t_plain.train_step(s_u, b)

        assert float(m_p["loss"]) == float(m_u["loss"])
        for name in ("off", "off:linear"):
            a, b_ = s_p.emb[name], s_u.emb[name]
            np.testing.assert_array_equal(np.asarray(a.keys),
                                          np.asarray(b_.keys))
            np.testing.assert_array_equal(np.asarray(a.weights),
                                          np.asarray(b_.weights))
            for sname in a.slots:
                np.testing.assert_array_equal(
                    np.asarray(a.slots[sname]),
                    np.asarray(b_.slots[sname]))
        for t in (tab_p, lin_p, tab_u, lin_u):
            t.finish()

    def test_steady_state_makes_no_per_step_device_reads(self, devices8):
        """The pipeline's steady state must never block on a device read:
        one blocking device_get per table per step serializes the tier
        (each read is a synchronous host<->device round trip —
        `python -m tools.offload_diag pipeline`). Overflow counters are cumulative on
        device and may be read ONLY at join points (flush/persist/
        restore/finish)."""
        from openembedding_tpu.parallel.mesh import create_mesh
        mesh = create_mesh(2, 4, devices8)
        # cache large enough that nothing evicts: eviction is a JOIN
        # (flush + rebuild) and is allowed to read the device
        trainer, table, lin = self._trainer(mesh, cache=4096)
        batches = self._batches(10)
        state = trainer.init(jax.random.PRNGKey(0),
                             trainer.shard_batch(batches[0]))
        # warm past compiles and the first inserts
        for b in batches[:2]:
            state, _ = trainer.train_step(state, b)

        # intercept every blocking-read spelling the codebase could use:
        # jax.device_get, jax.block_until_ready, and np.asarray/int(arr)
        # (both route through ArrayImpl.__array__)
        reads = []
        orig_get, orig_block = jax.device_get, jax.block_until_ready
        from jax._src import array as _jarray
        orig_arr = _jarray.ArrayImpl.__array__

        def counting_get(x):
            reads.append(f"device_get:{type(x).__name__}")
            return orig_get(x)

        def counting_block(x):
            reads.append(f"block_until_ready:{type(x).__name__}")
            return orig_block(x)

        def counting_array(self, *a, **kw):
            reads.append("ArrayImpl.__array__")
            return orig_arr(self, *a, **kw)

        jax.device_get = counting_get
        jax.block_until_ready = counting_block
        _jarray.ArrayImpl.__array__ = counting_array
        try:
            for i, b in enumerate(batches[2:]):
                nxt = batches[3 + i] if 3 + i < len(batches) else None
                state, _ = trainer.train_step(state, b, next_batch=nxt)
        finally:
            jax.device_get = orig_get
            jax.block_until_ready = orig_block
            _jarray.ArrayImpl.__array__ = orig_arr
        assert reads == [], \
            f"steady-state step made blocking device reads: {reads}"
        # the join point DOES read (and drains the overflow counter)
        table.flush(state.emb["off"])
        table._join_writeback()
        table.finish(); lin.finish()

    @pytest.mark.parametrize("depth", [
        pytest.param(1, marks=pytest.mark.slow),
        pytest.param(2, marks=pytest.mark.slow), 4])
    def test_pipelined_fit_matches_serial_steps(self, devices8, tmp_path,
                                                depth):
        """Bit-identical at EVERY lookahead depth: the planned-residency
        chain must make K prepares in flight equivalent to the serial
        order (the reference's prefetch ``steps`` budget is likewise a
        pure scheduling knob, exb_ops.cpp:148-156)."""
        from openembedding_tpu.parallel.mesh import create_mesh
        mesh = create_mesh(2, 4, devices8)
        batches = self._batches(8)

        # serial: explicit steps, no lookahead, blocking persist
        t_ser, tab_ser, lin_ser = self._trainer(mesh)
        s_ser = t_ser.init(jax.random.PRNGKey(0),
                           t_ser.shard_batch(batches[0]))
        for b in batches:
            s_ser, m_ser = t_ser.train_step(s_ser, b)
        tab_ser.flush(s_ser.emb["off"]); tab_ser._join_writeback()

        # pipelined: fit with lookahead + background persist
        t_pipe, tab_pipe, lin_pipe = self._trainer(mesh, depth=depth)
        s_pipe = t_pipe.init(jax.random.PRNGKey(0),
                             t_pipe.shard_batch(batches[0]))
        s_pipe, m_pipe = t_pipe.fit(s_pipe, batches,
                                    persist_dir=str(tmp_path / "p"))
        tab_pipe._join_persist()
        tab_pipe.flush(s_pipe.emb["off"]); tab_pipe._join_writeback()

        assert float(m_ser["loss"]) == pytest.approx(float(m_pipe["loss"]),
                                                     rel=1e-6)
        np.testing.assert_array_equal(tab_ser.host_weights,
                                      tab_pipe.host_weights)
        assert tab_ser.work_id == tab_pipe.work_id

        # the background persists committed a restorable chain
        tab_r = self._trainer(mesh)[1]
        c = tab_r.restore(str(tmp_path / "p" / "off"))
        assert tab_r.persisted_work > 0
        assert c.keys.shape[0] == tab_r.cache_capacity

    @pytest.mark.parametrize("depth", [
        2, pytest.param(4, marks=pytest.mark.slow)])
    def test_pipeline_survives_eviction_batches(self, devices8, depth):
        """A lookahead batch that would overflow the cache falls back to
        the synchronous evict path mid-pipeline, values staying exact —
        including the generation-bump recompute of the (depth-1) prepares
        that were in flight when the eviction rebuilt the cache."""
        from openembedding_tpu.parallel.mesh import create_mesh
        mesh = create_mesh(2, 4, devices8)
        batches = self._batches(10, seed=5)
        t_small, tab_small, _ = self._trainer(mesh, cache=256,
                                              depth=depth)  # evicts
        s = t_small.init(jax.random.PRNGKey(0),
                         t_small.shard_batch(batches[0]))
        s, _ = t_small.fit(s, batches)
        tab_small.flush(s.emb["off"]); tab_small._join_writeback()
        assert tab_small._gen > 0  # eviction really hit the pipeline

        t_big, tab_big, _ = self._trainer(mesh, cache=2048)  # never evicts
        s2 = t_big.init(jax.random.PRNGKey(0),
                        t_big.shard_batch(batches[0]))
        s2, _ = t_big.fit(s2, batches)
        tab_big.flush(s2.emb["off"]); tab_big._join_writeback()
        np.testing.assert_allclose(tab_small.host_weights,
                                   tab_big.host_weights,
                                   rtol=1e-5, atol=1e-6)


_KILL_CHILD = r"""
import os, signal, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import jax.numpy as jnp
sys.path.insert(0, {root!r})
from openembedding_tpu import EmbeddingVariableMeta
from openembedding_tpu.offload import HostOffloadedTable
from openembedding_tpu.utils import fs

t = HostOffloadedTable(
    EmbeddingVariableMeta(embedding_dim=4, vocabulary_size=1000),
    {{"category": "sgd", "learning_rate": 1.0}},
    {{"category": "constant", "value": 0.5}},
    vocab=1000, cache_capacity=256)
p = {pdir!r}
ids1 = np.array([1, 2, 3], np.int32)
t.prepare(ids1)
t.apply_gradients(jnp.asarray(ids1), jnp.ones((3, 4), jnp.float32))
t.persist(p)                               # committed base checkpoint
ids2 = np.array([10, 11], np.int32)
t.prepare(ids2)
t.apply_gradients(jnp.asarray(ids2), jnp.ones((2, 4), jnp.float32) * 2.0)

mode = {mode!r}
if mode == "mid_file":
    # SIGKILL while the incremental chain file's bytes are mid-write
    orig_write = fs._AtomicFile.write
    def dying_write(self, data):
        orig_write(self, bytes(data)[: max(1, len(data) // 2)])
        self._f.flush()
        os.kill(os.getpid(), signal.SIGKILL)
    fs._AtomicFile.write = dying_write
else:
    # chain file fully committed, SIGKILL before the meta commit point
    def dying_json(path, obj):
        os.kill(os.getpid(), signal.SIGKILL)
    fs.write_json_atomic = dying_json
    import openembedding_tpu.offload as off
    off.fs.write_json_atomic = dying_json
print("persisting", flush=True)
t.persist(p)                               # never returns
"""


@pytest.mark.parametrize("mode", [
    pytest.param("mid_file", marks=pytest.mark.slow), "pre_meta"])
def test_kill_mid_persist_restores_watermark(tmp_path, mode):
    """SIGKILL INSIDE persist (mid chain-file write / before the meta
    commit) must leave a restorable checkpoint at the PREVIOUS watermark —
    the reference's transactional pool-root commit
    (PmemEmbeddingItemPool.h:236-296). Restore ignores the debris; the
    next persist (the directory's single writer) GCs it."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pdir = str(tmp_path / "off")
    code = _KILL_CHILD.format(root=root, pdir=pdir, mode=mode)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == -9, (out.returncode, out.stdout, out.stderr)
    assert "persisting" in out.stdout

    # a fresh process restores the BASE state (pre-second-persist watermark)
    t2 = make_table()
    t2.restore(pdir)
    np.testing.assert_allclose(t2.host_weights[[1, 2, 3]], 0.5 - 1.0,
                               rtol=1e-6)
    # the second batch's update was never committed
    np.testing.assert_allclose(t2.host_weights[[10, 11]], 0.5)
    # the survivor trains on and persists: the writer-side sweep GCs the
    # crash debris, and the new chain is fully consistent
    ids3 = np.array([42], np.int32)
    t2.prepare(ids3)
    t2.apply_gradients(jnp.asarray(ids3), jnp.ones((1, DIM), jnp.float32))
    t2.persist(pdir)
    from openembedding_tpu import offload as off
    left = sorted(os.listdir(pdir))
    assert off.OFFLOAD_META_FILE in left
    import json
    with open(os.path.join(pdir, off.OFFLOAD_META_FILE)) as f:
        chain = {e["file"] for e in json.load(f)["checkpoints"]}
    assert set(left) == chain | {off.OFFLOAD_META_FILE}, (left, chain)
    t3 = make_table()
    t3.restore(pdir)
    np.testing.assert_allclose(t3.host_weights[42], 0.5 - 1.0, rtol=1e-6)


def test_persist_restore_remote_uri(tmp_path):
    """Offload persistence streams to fsspec URIs like the checkpoint dump
    (memory:// stands in for gs://; the reference persists its PMem pool
    through the same remote-capable file layer)."""
    import uuid
    uri = f"memory://off-{uuid.uuid4().hex}"
    t = make_table()
    ids = np.array([1, 2, 3], np.int32)
    t.prepare(ids)
    t.apply_gradients(jnp.asarray(ids), jnp.ones((3, DIM), jnp.float32))
    t.persist(uri)
    ids2 = np.array([7], np.int32)
    t.prepare(ids2)
    t.apply_gradients(jnp.asarray(ids2), jnp.ones((1, DIM), jnp.float32))
    info = t.persist(uri)
    assert info["file"].startswith("inc_")
    t2 = make_table()
    t2.restore(uri)
    np.testing.assert_allclose(t2.host_weights, t.host_weights, rtol=1e-6)


_PIPELINE_KILL_CHILD = r"""
import sys
sys.path.insert(0, {root!r})
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
import numpy as np
import optax
from openembedding_tpu import (EmbeddingCollection, EmbeddingVariableMeta,
                               Trainer)
from openembedding_tpu.models import deepctr
from openembedding_tpu.offload import ShardedOffloadedTable
from openembedding_tpu.parallel.mesh import create_mesh

mesh = create_mesh(2, 4)
table = ShardedOffloadedTable(
    "off", EmbeddingVariableMeta(embedding_dim=1, vocabulary_size=2048),
    {{"category": "adagrad", "learning_rate": 0.1}},
    {{"category": "constant", "value": 0.25}},
    vocab=2048, cache_capacity=256, mesh=mesh, persist_pending_window=2)
coll = EmbeddingCollection((table.embedding_spec(name="off:linear"),),
                           mesh)
trainer = Trainer(deepctr.LogisticRegression(feature_names=("off",)),
                  coll, optax.sgd(0.1), offload={{"off:linear": table}},
                  pipeline_depth=3)
rng = np.random.RandomState(11)
batches = []
for i in range(40):
    lo = (i * 300) % 1600
    ids = rng.randint(lo, lo + 400, 64).astype(np.int32)
    batches.append({{"label": (ids % 2).astype(np.float32), "dense": None,
                   "sparse": {{"off:linear": ids}}}})
state = trainer.init(jax.random.PRNGKey(0), trainer.shard_batch(batches[0]))
trainer.fit(state, batches, log_every=1, persist_dir={pdir!r})
print("FINISHED", flush=True)
"""


@pytest.mark.slow
def test_kill_mid_pipelined_fit_resume_exact(tmp_path):
    """SIGKILL a child mid-``fit`` with the WHOLE pipeline in flight —
    depth-3 lookahead prepares, async writeback, async incremental
    persist — then restore from the committed chain and RESUME from the
    committed watermark: the resumed run must land bit-identical to an
    uninterrupted serial run of the same batches (the reference's
    restore-and-continue contract around its transactional PMem commits,
    PmemEmbeddingItemPool.h:236-296)."""
    import os
    import signal as signal_mod
    import subprocess
    import sys
    import jax
    import optax
    from openembedding_tpu import (EmbeddingCollection, Trainer)
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.offload import ShardedOffloadedTable
    from openembedding_tpu.parallel.mesh import create_mesh

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pdir = str(tmp_path / "p")
    code = _PIPELINE_KILL_CHILD.format(root=root, pdir=pdir)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    # -u: the child's fit() log lines must stream UNBUFFERED — with the
    # default block buffering every line arrives only at exit and the
    # SIGKILL would land on an already-finished child (a vacuous test)
    proc = subprocess.Popen([sys.executable, "-u", "-c", code], env=env,
                            stdout=subprocess.PIPE, text=True)
    # kill mid-run: after step 15 the depth-3 window is full, the async
    # persister has fired ~7 times, and writebacks ride evictions
    killed = False
    for line in proc.stdout:
        if line.startswith("step 15:"):
            proc.send_signal(signal_mod.SIGKILL)
            killed = True
            break
        assert not line.startswith("FINISHED"), "child outran the kill"
    assert killed, "child died before step 15"
    assert proc.wait() == -9, "child was not killed mid-run"

    def make_parts(depth):
        mesh = create_mesh(2, 4, jax.devices()[:8])
        from openembedding_tpu import EmbeddingVariableMeta
        table = ShardedOffloadedTable(
            "off", EmbeddingVariableMeta(embedding_dim=1,
                                         vocabulary_size=2048),
            {"category": "adagrad", "learning_rate": 0.1},
            {"category": "constant", "value": 0.25},
            vocab=2048, cache_capacity=256, mesh=mesh,
            persist_pending_window=2)
        coll = EmbeddingCollection(
            (table.embedding_spec(name="off:linear"),), mesh)
        trainer = Trainer(
            deepctr.LogisticRegression(feature_names=("off",)),
            coll, optax.sgd(0.1), offload={"off:linear": table},
            pipeline_depth=depth)
        return trainer, table

    rng = np.random.RandomState(11)
    batches = []
    for i in range(40):
        lo = (i * 300) % 1600
        ids = rng.randint(lo, lo + 400, 64).astype(np.int32)
        batches.append({"label": (ids % 2).astype(np.float32),
                        "dense": None, "sparse": {"off:linear": ids}})

    # serial reference: snapshot (host store, params) after every batch
    t_ref, tab_ref = make_parts(1)
    s_ref = t_ref.init(jax.random.PRNGKey(0),
                       t_ref.shard_batch(batches[0]))
    snaps = {}
    for b in batches:
        s_ref, _ = t_ref.train_step(s_ref, b)
        tab_ref.flush(s_ref.emb["off:linear"])
        tab_ref._join_writeback()
        snaps[tab_ref.work_id] = (
            tab_ref.host_weights.copy(),
            {k: v.copy() for k, v in tab_ref.host_slots.items()},
            jax.tree.map(lambda x: np.asarray(x).copy(), s_ref.params))

    # restore: the chain must be consistent at SOME committed watermark
    t_res, tab_res = make_parts(3)
    cache = tab_res.restore(os.path.join(pdir, "off:linear"))
    w = tab_res.persisted_work
    assert w in snaps and w >= 3, f"watermark {w} not a batch boundary"
    # the kill landed MID-run: there must be committed-but-incomplete
    # progress, i.e. real batches left for the resume to replay
    assert w <= 20, f"watermark {w}: child finished before the kill"
    ref_weights, ref_slots, ref_params = snaps[w]
    np.testing.assert_array_equal(tab_res.host_weights, ref_weights)
    for k in ref_slots:
        np.testing.assert_array_equal(tab_res.host_slots[k], ref_slots[k])

    # resume from the watermark with the reference's dense params: the
    # continued run must land exactly where the uninterrupted run did
    s2 = t_res.init(jax.random.PRNGKey(0), t_res.shard_batch(batches[0]))
    s2 = s2.replace(emb={"off:linear": cache},
                    params=jax.tree.map(jnp.asarray, ref_params))
    done = w - 1    # work_id w  <=>  w-1 batches committed
    s2, _ = t_res.fit(s2, batches[done:])
    tab_res.flush(s2.emb["off:linear"])
    tab_res._join_writeback()
    np.testing.assert_array_equal(tab_res.host_weights,
                                  tab_ref.host_weights)
    for k in tab_ref.host_slots:
        np.testing.assert_array_equal(tab_res.host_slots[k],
                                      tab_ref.host_slots[k])


@pytest.mark.slow
def test_hand_driven_prefetch_matches_fit(devices8):
    """The PUBLIC prefetch API (the bench's hand-driven pattern:
    ``prefetch(window); train_step(batch)``) is the same pipeline fit
    wires — bit-identical results."""
    inst = TestPipelinedOffload()
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)
    batches = inst._batches(8, seed=9)

    t_fit, tab_fit, _ = inst._trainer(mesh, depth=2)
    s = t_fit.init(jax.random.PRNGKey(0), t_fit.shard_batch(batches[0]))
    s, _ = t_fit.fit(s, batches)
    tab_fit.flush(s.emb["off"]); tab_fit._join_writeback()

    t_hand, tab_hand, _ = inst._trainer(mesh, depth=2)
    s2 = t_hand.init(jax.random.PRNGKey(0), t_hand.shard_batch(batches[0]))
    for i in range(len(batches)):
        t_hand.prefetch(batches[i:i + 3])
        s2, _ = t_hand.train_step(s2, batches[i])
    tab_hand.finish()
    tab_hand.flush(s2.emb["off"]); tab_hand._join_writeback()
    np.testing.assert_array_equal(tab_fit.host_weights,
                                  tab_hand.host_weights)


def test_persist_compress_chain(tmp_path, devices8):
    """A zlib persist chain restores identically to a raw one, raw and
    compressed entries can share a chain, and the compressed files are
    smaller on compressible (constant-init) stores."""
    import os
    from openembedding_tpu.offload import ShardedOffloadedTable
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)

    def mk(compress):
        return ShardedOffloadedTable(
            "t", EmbeddingVariableMeta(embedding_dim=4,
                                       vocabulary_size=512),
            {"category": "sgd", "learning_rate": 1.0},
            {"category": "constant", "value": 0.5},
            vocab=512, cache_capacity=128, mesh=mesh,
            persist_compress=compress)

    stores = {}
    for codec in ("", "zlib"):
        t = mk(codec)
        c = t.create_cache()
        ids = np.arange(0, 40, dtype=np.int32)
        c = t.prepare(c, ids)
        t.note_update(ids)
        c2 = t.prepare(c, np.arange(40, 60, dtype=np.int32))
        t.note_update(np.arange(40, 60, dtype=np.int32))
        d = str(tmp_path / f"chain{codec}")
        t.persist(c2, d)                       # base
        ids3 = np.arange(60, 70, dtype=np.int32)
        c3 = t.prepare(c2, ids3)
        t.note_update(ids3)
        t.persist(c3, d)                       # delta
        stores[codec] = d

    # compressed chain is materially smaller (constant-init rows)
    size = {c: sum(os.path.getsize(os.path.join(d, f))
                   for f in os.listdir(d)) for c, d in stores.items()}
    assert size["zlib"] < size[""] * 0.5, size

    r_raw, r_z = mk(""), mk("")
    r_raw.restore(stores[""])
    r_z.restore(stores["zlib"])
    np.testing.assert_array_equal(r_raw.host_weights, r_z.host_weights)
    assert r_raw.persisted_work == r_z.persisted_work

    # mixed chain: a raw table appends a raw delta onto the zlib chain
    t2 = mk("")
    c = t2.restore(stores["zlib"])
    ids4 = np.arange(70, 80, dtype=np.int32)
    c = t2.prepare(c, ids4)
    t2.note_update(ids4)
    t2.persist(c, stores["zlib"])
    t3 = mk("zlib")
    t3.restore(stores["zlib"])
    assert t3.persisted_work == t2.work_id


@pytest.mark.slow
def test_pipeline_parity_under_timing_fuzz(devices8):
    """Randomized host-gather delays shift every prepare/apply/evict
    interleaving; results must stay bit-identical to serial regardless
    (the planned-residency books + generation protocol, not luck, carry
    the correctness). Small cache so evictions and stale-generation
    recomputes fire mid-window."""
    import time as time_mod
    inst = TestPipelinedOffload()
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)
    batches = inst._batches(12, seed=13)

    t_ser, tab_ser, lin_ser = inst._trainer(mesh, cache=256)
    s = t_ser.init(jax.random.PRNGKey(0), t_ser.shard_batch(batches[0]))
    for b in batches:
        s, _ = t_ser.train_step(s, b)
    tab_ser.flush(s.emb["off"]); tab_ser._join_writeback()
    lin_ser.flush(s.emb["off:linear"]); lin_ser._join_writeback()

    fuzz = np.random.RandomState(99)
    t_f, tab_f, lin_f = inst._trainer(mesh, cache=256, depth=4)
    for t in (tab_f, lin_f):
        orig = t._gather_host

        def jittery(ids, _orig=orig):
            time_mod.sleep(float(fuzz.uniform(0, 0.03)))
            return _orig(ids)

        t._gather_host = jittery
    s2 = t_f.init(jax.random.PRNGKey(0), t_f.shard_batch(batches[0]))
    s2, _ = t_f.fit(s2, batches)
    tab_f.flush(s2.emb["off"]); tab_f._join_writeback()
    lin_f.flush(s2.emb["off:linear"]); lin_f._join_writeback()
    assert tab_f.evictions > 0
    # NOTE: the generation-RETRY paths rarely fire here — the budget check
    # runs against resident+planned, so once the window overflows, later
    # prepares degrade to needs_evict instead of gathering at a soon-stale
    # generation. The deterministic tests below force those paths.
    np.testing.assert_array_equal(tab_ser.host_weights, tab_f.host_weights)
    np.testing.assert_array_equal(lin_ser.host_weights, lin_f.host_weights)


def _mk_sharded(mesh, vocab=2048, cache=256):
    from openembedding_tpu.offload import ShardedOffloadedTable
    return ShardedOffloadedTable(
        "t", EmbeddingVariableMeta(embedding_dim=4, vocabulary_size=vocab),
        {"category": "sgd", "learning_rate": 1.0},
        {"category": "constant", "value": 0.25},
        vocab=vocab, cache_capacity=cache, mesh=mesh)


def test_stale_prepare_recomputed_at_apply(devices8):
    """A prepare computed before an eviction must be RECOMPUTED at its
    apply (generation mismatch), in batch-order priority over any
    lookahead claims — applying it verbatim would insert rows the
    rebuild dropped and resurrect pre-eviction host values."""
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)
    t = _mk_sharded(mesh)
    cache = t.create_cache()
    # in-budget prepare at generation 0 (planned marks set)
    ids_a = np.arange(0, 50, dtype=np.int32)
    prep_a = t.host_prepare(ids_a)
    assert not prep_a.needs_evict and prep_a.gen == 0
    # a second prepare overflows the budget -> needs_evict; applying it
    # FIRST (out of order, table-level API permits it) rebuilds the cache
    ids_b = np.arange(100, 100 + 160, dtype=np.int32)
    prep_b = t.host_prepare(ids_b)
    assert prep_b.needs_evict
    cache = t.apply_prepared(cache, prep_b)
    assert t.evictions == 1 and t._gen == 1
    # prep_a is now stale: its apply must take the recompute path
    cache = t.apply_prepared(cache, prep_a)
    assert t.gen_retries >= 1
    assert bool(t._resident[ids_a].all())
    # values: cache rows for ids_a equal host rows (insert really landed)
    from openembedding_tpu.parallel import sharded_hash as sh
    got = np.asarray(sh.pull_sharded(cache, jnp.asarray(ids_a), None,
                                     mesh=mesh, spec=t.spec,
                                     batch_sharded=False))
    np.testing.assert_array_equal(got, t.host_weights[ids_a])


def test_gather_retry_when_evicted_mid_gather(devices8):
    """An eviction landing while a lookahead gather is in flight must
    force that host_prepare to retry at the new generation (the torn
    read would otherwise mark planned rows against dropped residency)."""
    import threading
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)
    t = _mk_sharded(mesh)
    cache = t.create_cache()
    # sized against budget 0.7*256=179 with keep_fraction 0.5 (keep 89):
    # 135 warm + 40 prep = 175 fits (the prep GATHERS, parking in the
    # patch); 135 + 45 big = 180 overflows (big evicts); post-evict
    # 89 kept + 45 + 40 retried = 174 fits (the retry lands in-budget)
    warm = np.arange(0, 135, dtype=np.int32)
    cache = t.prepare(cache, warm)
    t.note_update(warm)

    in_gather = threading.Event()
    release = threading.Event()
    orig = t._gather_host
    fired = []

    def blocking_gather(ids):
        if not fired:
            fired.append(True)
            in_gather.set()
            release.wait(timeout=30)
        return orig(ids)

    t._gather_host = blocking_gather
    out = {}

    def prep_thread():
        out["prep"] = t.host_prepare(np.arange(200, 240, dtype=np.int32))

    th = threading.Thread(target=prep_thread)
    th.start()
    assert in_gather.wait(timeout=30)
    # eviction on the main thread while the gather is parked
    big = t.host_prepare(np.arange(300, 345, dtype=np.int32))
    assert big.needs_evict
    ev = threading.Thread(target=lambda: out.update(
        cache2=t.apply_prepared(cache, big)))
    ev.start()
    # the evictor never needs the parked gather (the prep thread holds no
    # lock while parked), so it can run to completion first — POLL for
    # the generation bump instead of racing a sleep against JIT/IO time
    import time as time_mod
    deadline = time_mod.time() + 60
    while t._gen == 0 and time_mod.time() < deadline:
        time_mod.sleep(0.01)
    assert t._gen == 1, "eviction did not complete"
    release.set()
    th.join(timeout=60); ev.join(timeout=60)
    assert not th.is_alive() and not ev.is_alive()
    prep = out["prep"]
    # the parked gather's generation went stale; the retry recomputed at
    # the post-eviction generation
    assert t.gen_retries >= 1
    assert prep.gen == t._gen and not prep.needs_evict
    t.cancel_prepared(prep)
    assert t._planned_count == 0


def test_overflow_check_every_n_batches(devices8):
    """Bounded-lag overflow detection (ADVICE r5): with the knob set, a
    deferred insert overflow surfaces within N note_update calls — not
    only at finish() — so hand-driven loops and fit() without persist_dir
    keep a bounded detection lag."""
    from openembedding_tpu.offload import ShardedOffloadedTable
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)
    t = ShardedOffloadedTable(
        "t", EmbeddingVariableMeta(embedding_dim=4, vocabulary_size=512),
        {"category": "sgd", "learning_rate": 1.0},
        {"category": "constant", "value": 0.25},
        vocab=512, cache_capacity=256, mesh=mesh,
        overflow_check_every_n_batches=3)
    t._overflow_latest = jnp.asarray(1, jnp.int32)  # deferred evidence
    ids = np.array([1, 2], np.int32)
    t.note_update(ids)
    t.note_update(ids)  # lag stays below N: no device read yet
    with pytest.raises(RuntimeError, match="insert overflow"):
        t.note_update(ids)
    # evidence drained by the raise; the run can unwind through finish()
    t.finish()


def test_check_overflow_prefers_live_cache_counter(devices8):
    """flush (and _evict/persist) read the LIVE cache.insert_failures
    (ADVICE r5): failures accumulated by the jitted step's gradient-apply
    auto-insert AFTER the last host-side insert are caught even though
    the _overflow_latest copy never saw them."""
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)
    t = _mk_sharded(mesh)
    cache = t.create_cache(jax.random.PRNGKey(0))
    assert t._overflow_latest is None  # no host-side insert happened
    poisoned = cache.replace(insert_failures=jnp.asarray(2, jnp.int32))
    with pytest.raises(RuntimeError, match="insert overflow"):
        t.flush(poisoned)
    # a clean cache passes, and the copy (None) is not consulted
    assert t.flush(cache) == 0
    t._join_writeback()


# --- in-place inserts, the public load and warm calls, one insert size -------

def _pull(t, cache, ids, mesh):
    from openembedding_tpu.parallel import sharded_hash as sh
    return np.asarray(sh.pull_sharded(cache, jnp.asarray(ids), None,
                                      mesh=mesh, spec=t.spec,
                                      batch_sharded=False))


def _aliased_operands(program, *args):
    """Numbers of the operands that the lowered program donates and that
    its compiled outputs alias, output n to operand n."""
    import re
    lowered = program.lower(*args)
    donors = [int(n) for n in re.findall(
        r"%arg(\d+): tensor<[^>]*> \{[^}]*jax\.buffer_donor = true",
        lowered.as_text())]
    header = next(line for line in lowered.compile().as_text().splitlines()
                  if line.startswith("HloModule"))
    aliased = [int(o) for o, i in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", header)
        if o == i]
    assert sorted(donors) == sorted(aliased), (donors, header)
    return sorted(aliased)


@pytest.mark.parametrize("packed", [True, False],
                         ids=["packed", "unpacked"])
def test_insert_program_updates_the_table_in_place(devices8, packed):
    """Keys, weights and every slot of the table are donated to the insert
    program and its outputs alias them: no call returns a second table."""
    from openembedding_tpu.parallel import sharded_hash as sh
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)
    t = _mk_sharded(mesh)
    cache = t.create_cache()
    n, dim = 64, 4
    if packed:
        _, columns, layout = t._packed_layout(np.dtype(np.int32))
        program = sh._insert_packed_program(mesh, t.spec, dim, layout)
        rows = (jnp.zeros((n, columns), jnp.float32),)
    else:
        program = sh._insert_rows_program(mesh, t.spec, tuple(cache.slots),
                                          tuple(cache.slots))
        rows = (jnp.zeros((n,), jnp.int32), jnp.zeros((n, dim)),
                {k: jnp.zeros((n,) + v.shape[1:], v.dtype)
                 for k, v in cache.slots.items()})
    operands = _aliased_operands(program, cache.keys, cache.weights,
                                 cache.slots, cache.insert_failures,
                                 cache.init_rng, *rows)
    assert operands == list(range(2 + len(cache.slots))), operands


@pytest.mark.parametrize("donate", [True, False])
def test_bulk_insert_donates_unless_told_not_to(devices8, donate):
    """``insert_rows_sharded`` consumes the state it is given; the serving
    hot-swap, whose readers hold the old state, asks for a copy."""
    from openembedding_tpu.parallel import sharded_hash as sh
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)
    t = _mk_sharded(mesh)
    old = t.create_cache()
    ids = np.arange(32, dtype=np.int32)
    new = sh.insert_rows_sharded(
        old, jnp.asarray(ids), jnp.ones((32, 4), jnp.float32),
        {k: jnp.zeros((32,) + v.shape[1:], v.dtype)
         for k, v in old.slots.items()},
        mesh=mesh, spec=t.spec, donate=donate)
    assert old.keys.is_deleted() == donate
    assert old.weights.is_deleted() == donate
    np.testing.assert_array_equal(_pull(t, new, ids, mesh),
                                  np.ones((32, 4), np.float32))


@pytest.mark.parametrize("ids", [slice(100, 356), np.arange(900, 100, -7)],
                         ids=["range", "array"])
def test_load_rows_then_warm_serves_the_loaded_rows(devices8, ids):
    """``load_rows`` writes known rows and slot rows into the store by id
    range or id array; ``warm`` makes them resident in bulk, the books as
    ``apply_prepared`` leaves them, and a later prepare misses nothing."""
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)
    t = _mk_sharded(mesh, cache=4096)
    which = np.arange(t.vocab)[ids]
    rows = np.random.RandomState(0).randn(which.size, 4).astype(np.float32)
    t.load_rows(ids, rows)
    np.testing.assert_array_equal(t.host_weights[which], rows)
    assert (t.host_work_id[which] == t.work_id).all()
    old = t.create_cache()
    cache = t.warm(old, which)
    assert old.keys.is_deleted()            # updated in place
    assert t._resident_count == which.size and t._resident[which].all()
    assert (t._last_touch[which] == t.work_id).all()
    np.testing.assert_array_equal(_pull(t, cache, which, mesh), rows)
    prep = t.host_prepare(which[:50])
    assert prep.missing.size == 0 and not prep.needs_evict
    t.cancel_prepared(prep)
    with pytest.raises(ValueError, match="the cache holds"):
        t.load_rows(ids, rows)
    t.finish()


def test_warm_refuses_what_passes_the_budget(devices8):
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)
    t = _mk_sharded(mesh, cache=256)        # budget 179 rows
    cache = t.create_cache()
    with pytest.raises(ValueError, match="budget"):
        t.warm(cache, np.arange(200))
    assert t._resident_count == 0 and not cache.keys.is_deleted()


def test_step_insert_has_one_size_while_the_misses_fit(devices8):
    """Miss counts differ from step to step; the insert between two steps
    is padded to one size for a table (the largest power of two in an
    eighth of the batch's lookups), so a stream of batches of one shape
    compiles it once. Only a step that misses more meets the second."""
    from openembedding_tpu import offload
    from openembedding_tpu.parallel import sharded_hash as sh
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)
    t = _mk_sharded(mesh, vocab=1 << 16, cache=1 << 15)
    assert t._step_insert_size(106496, 5000) == 8192
    assert t._step_insert_size(106496, 8192) == 8192
    assert t._step_insert_size(106496, 8193) == offload.STEP_CHUNK
    assert t._step_insert_size(64, 3) == 32
    cache = t.create_cache()
    rng = np.random.RandomState(1)
    compiled = []

    def on(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    lo, sizes = 0, []
    for misses in (300, 17, 511, 1, 256, 90):
        # 4,096 lookups a batch (size 512): fresh ids, the rest repeats
        ids = np.concatenate([np.arange(lo, lo + misses),
                              rng.randint(0, max(lo, 1), 4096 - misses)])
        lo += misses
        before = len(compiled)
        cache = t.prepare(cache, ids.astype(np.int32))
        sizes.append(len(compiled) - before)
    program = sh._insert_packed_program(      # the cached one, by its key
        mesh, t.spec, 4, t._packed_layout(np.dtype(np.int32))[2], False)
    assert program._cache_size() == 1
    assert sizes[1:] == [0] * 5, sizes      # every compile was the first's
    ids = np.arange(lo, lo + 600).astype(np.int32)      # more than 512
    ids = np.concatenate([ids, np.zeros(4096 - 600, np.int32)])
    cache = t.prepare(cache, ids)
    assert program._cache_size() == 2
    assert t._resident_count == lo + 600
    t.finish()


@pytest.mark.parametrize("writeback", ["ahead", "chunk_by_chunk"])
def test_eviction_rebuilds_the_cache_in_its_own_buffers(devices8, writeback,
                                                        monkeypatch):
    """Eviction empties and refills the cache it is given (donated): no
    second cache is allocated beside the first, and what it wrote back
    and re-inserted reads exactly as last written. Both ways of the
    write-back: every read dispatched ahead of a writer thread, and (more
    dirty rows than ``WRITEBACK_ASYNC_ROWS``) chunk by chunk, one read
    ahead of the chunk being stored, before the call returns."""
    from openembedding_tpu import offload
    from openembedding_tpu.analysis import scope
    from openembedding_tpu.parallel.mesh import create_mesh
    if writeback == "chunk_by_chunk":       # 150 dirty rows: five chunks
        monkeypatch.setattr(offload, "WRITEBACK_ASYNC_ROWS", 64)
        monkeypatch.setattr(offload, "WRITEBACK_CHUNK", 32)
    mesh = create_mesh(2, 4, devices8)
    t = _mk_sharded(mesh)                   # budget 179 rows
    before = scope.HISTOGRAMS.counter("offload_evictions", table="t")
    cache = t.prepare(t.create_cache(), np.arange(0, 150, dtype=np.int32))
    grads = np.ones((150, 4), np.float32)
    from openembedding_tpu.parallel import sharded_hash as sh
    cache = sh.apply_gradients_sharded(
        cache, t.optimizer, t.initializer,
        jnp.asarray(np.arange(0, 150, dtype=np.int32)), jnp.asarray(grads),
        mesh=mesh, spec=t.spec, batch_sharded=False)
    t.note_update(np.arange(0, 150))
    full = cache
    cache = t.prepare(cache, np.arange(500, 600, dtype=np.int32))
    assert t.evictions == 1 and full.keys.is_deleted()
    assert scope.HISTOGRAMS.counter("offload_evictions", table="t") \
        == before + 1
    assert t._writer is None and not t._dirty.mask_rows(np.arange(150)).any()
    assert int(cache.insert_failures) == 0
    np.testing.assert_array_equal(t.host_weights[:150],
                                  np.full((150, 4), -0.75, np.float32))
    kept = np.nonzero(t._resident[:150])[0]
    assert kept.size
    np.testing.assert_array_equal(_pull(t, cache, kept, mesh),
                                  t.host_weights[kept])
    t.finish()


def test_fit_leaves_the_tiers_spans_and_counters(devices8):
    """A fit with ``offload=`` feeds the tier's four spans (the wait for
    the lookahead thread among them) and its row and byte counters, a
    table; no eviction, no retry."""
    from openembedding_tpu.analysis import scope
    from openembedding_tpu.parallel.mesh import create_mesh
    mesh = create_mesh(2, 4, devices8)
    helper = TestPipelinedOffload()
    trainer, table, lin = helper._trainer(mesh, cache=4096)
    batches = helper._batches(6)
    H = scope.HISTOGRAMS
    spans = {"offload.host_prepare": {"table": "off"},
             "offload.wait_prepare": {},
             "offload.apply_prepared": {"table": "off"},
             "offload.note_update": {"table": "off:linear"}}
    series = {k: "span_" + k.replace(".", "_") + "_seconds" for k in spans}
    calls = {k: H.count(series[k], **l) for k, l in spans.items()}
    counts = {c: H.counter(c, table="off") for c in
              ("offload_miss_rows", "offload_unique_rows",
               "offload_h2d_bytes", "offload_evictions",
               "offload_gen_retries")}
    state = trainer.init(jax.random.PRNGKey(0),
                         trainer.shard_batch(batches[0]))
    state, _ = trainer.fit(state, batches)
    for k, l in spans.items():
        assert H.count(series[k], **l) - calls[k] >= 6, k
    grew = {c: H.counter(c, table="off") - v for c, v in counts.items()}
    assert grew["offload_unique_rows"] >= grew["offload_miss_rows"] > 0
    assert grew["offload_miss_rows"] == table._resident_count
    # one packed buffer a step: 32 keys x (key + row + accumulator) floats
    assert grew["offload_h2d_bytes"] % (9 * 4) == 0 \
        and grew["offload_h2d_bytes"] > 0
    assert grew["offload_evictions"] == grew["offload_gen_retries"] == 0

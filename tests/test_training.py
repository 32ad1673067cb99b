"""End-to-end training stack: EmbeddingCollection + Trainer + model zoo.

The analogue of the reference's examples-as-tests strategy (SURVEY §4:
build.sh unit_test runs the example models end to end): synthetic criteo-like
batches through every model family on a (data, model) mesh, asserting the
jitted step runs, loss decreases, and mixed array+hash collections work.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from openembedding_tpu import EmbeddingCollection, EmbeddingSpec, Trainer
from openembedding_tpu.models import deepctr
from openembedding_tpu.parallel.mesh import create_mesh

FEATURES = ("c0", "c1", "c2")
VOCAB = 100
DIM = 8
B = 16


def synthetic_batches(n, seed=0, hash_keys=False):
    """Clickable synthetic task: label depends on feature parity."""
    rng = np.random.RandomState(seed)
    for _ in range(n):
        sparse = {}
        raw = {}
        for f in FEATURES:
            ids = rng.randint(0, VOCAB, size=B).astype(np.int32)
            raw[f] = ids
            key = ((ids.astype(np.int64) * 2654435761) % (2**31)
                   if hash_keys else ids)
            sparse[f] = key.astype(np.int32)
            sparse[f + deepctr.LINEAR_SUFFIX] = sparse[f]
        label = ((raw["c0"] + raw["c1"]) % 2).astype(np.float32)
        dense = rng.randn(B, 4).astype(np.float32)
        yield {"label": label, "dense": dense, "sparse": sparse}


def build_trainer(model_name, mesh, vocab=VOCAB, **spec_kw):
    specs = deepctr.make_feature_specs(FEATURES, vocab, DIM, **spec_kw)
    coll = EmbeddingCollection(
        specs, mesh,
        default_optimizer={"category": "adagrad", "learning_rate": 0.1})
    model = deepctr.build_model(model_name, FEATURES)
    return Trainer(model, coll, optax.adam(1e-2))


@pytest.mark.parametrize("model_name", [
    pytest.param("lr", marks=pytest.mark.xfail(
        strict=False,
        reason="jax 0.4.37: lr loss drifts upward (0.80->0.81) instead of "
               "decreasing — the synthetic label (c0+c1)%2 is XOR parity, "
               "which a linear model cannot fit (no interaction term; the "
               "deep models memorize it through their towers); earlier jax "
               "images passed on init/optimizer noise. A learnable-task lr "
               "check lives in test_auc_lift_on_learnable_task.")),
    "deepfm",
    # tier-1 budget (COVERAGE.md): deepfm exercises the shared
    # linear+fields+MLP path; the variant towers ride the slow lane
    pytest.param("wdl", marks=pytest.mark.slow),
    pytest.param("xdeepfm", marks=pytest.mark.slow),
    pytest.param("dcn", marks=pytest.mark.slow)])
def test_model_zoo_trains(devices8, model_name):
    mesh = create_mesh(2, 4, devices8)
    trainer = build_trainer(model_name, mesh)
    batches = list(synthetic_batches(30))
    state = trainer.init(jax.random.PRNGKey(0), trainer.shard_batch(batches[0]))
    losses = []
    for b in batches:
        state, m = trainer.train_step(state, b)
        losses.append(float(m["loss"]))
    assert int(state.step) == 30
    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    assert last < first, (first, last)
    # eval produces probabilities
    p = np.asarray(trainer.eval_step(state, batches[0]))
    assert p.shape == (B,) and (p >= 0).all() and (p <= 1).all()


@pytest.mark.slow
def test_hash_collection_trains(devices8):
    """input_dim=-1 features ride the hash-table path inside the same step.
    Slow lane (tier-1 budget): the fused hash path trains in tier-1 via
    test_fused.py::test_fused_hash_training."""
    mesh = create_mesh(2, 4, devices8)
    trainer = build_trainer("deepfm", mesh, vocab=-1, hash_capacity=4096)
    batches = list(synthetic_batches(20, hash_keys=True))
    state = trainer.init(jax.random.PRNGKey(0), trainer.shard_batch(batches[0]))
    losses = []
    for b in batches:
        state, m = trainer.train_step(state, b)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    for f in FEATURES:
        assert int(state.emb[f].insert_failures) == 0


def test_mixed_array_and_hash(devices8):
    mesh = create_mesh(1, 8, devices8)
    specs = (EmbeddingSpec(name="a", input_dim=VOCAB, output_dim=DIM),
             EmbeddingSpec(name="b", input_dim=-1, output_dim=DIM,
                           hash_capacity=1024))
    coll = EmbeddingCollection(specs, mesh)
    states = coll.init(jax.random.PRNGKey(1))
    idx = {"a": jnp.arange(8, dtype=jnp.int32),
           "b": jnp.arange(8, dtype=jnp.int32) * 7 + 3}
    rows = coll.pull(states, idx, batch_sharded=False)
    assert rows["a"].shape == (8, DIM) and rows["b"].shape == (8, DIM)
    grads = {k: jnp.ones_like(v) for k, v in rows.items()}
    new_states = coll.apply_gradients(states, idx, grads, batch_sharded=False)
    # both variables actually moved
    for k in ("a", "b"):
        assert not np.allclose(np.asarray(rows[k]),
                               np.asarray(coll.pull(new_states, idx,
                                                    batch_sharded=False)[k]))


def test_int64_keys_require_int64_table(devices8):
    """int64 queries against an EXPLICIT int32-keyed table must refuse,
    not alias mod 2^32; the DEFAULT (wide) table accepts them at full
    width — even from a host int64 column with x64 OFF."""
    mesh = create_mesh(1, 8, devices8)
    specs = (EmbeddingSpec(name="h", input_dim=-1, output_dim=4,
                           hash_capacity=64, key_dtype="int32"),)
    coll = EmbeddingCollection(specs, mesh)
    states = coll.init()
    big = np.array([2**33 + 7], dtype=np.int64)
    # without x64, jnp.asarray itself truncates int64 -> int32 before the
    # table ever sees the key, so the aliasing guard only engages under x64
    with jax.enable_x64(True):
        with pytest.raises(ValueError, match="key_dtype"):
            coll.pull(states, {"h": jnp.asarray(big)}, batch_sharded=False)

    # the wide DEFAULT holds the full key: a host int64 column splits on
    # host (x64 off) and addresses the same row as explicit split64 pairs
    from openembedding_tpu import hash_table as hl
    wcoll = EmbeddingCollection(
        (EmbeddingSpec(name="h", input_dim=-1, output_dim=4,
                       hash_capacity=64,
                       initializer={"category": "normal", "stddev": 1.0},
                       optimizer={"category": "sgd",
                                  "learning_rate": 1.0}),), mesh)
    assert wcoll.specs["h"].key_dtype == "wide"
    ws = wcoll.init()
    ws = wcoll.apply_gradients(ws, {"h": big},
                               {"h": jnp.ones((1, 4), jnp.float32)},
                               batch_sharded=False)
    keys = np.asarray(jax.device_get(ws["h"].keys))
    live = keys[keys[..., 1] != hl.empty_key(np.int32)]
    assert set(hl.join64(live.reshape(-1, 2))) == {2**33 + 7}  # not 7!
    via_col = wcoll.pull(ws, {"h": big}, batch_sharded=False)["h"]
    via_pairs = wcoll.pull(ws, {"h": jnp.asarray(hl.split64(big))},
                           batch_sharded=False)["h"]
    np.testing.assert_array_equal(np.asarray(via_col),
                                  np.asarray(via_pairs))


def test_collection_meta_and_duplicate_names(devices8):
    mesh = create_mesh(1, 8, devices8)
    specs = deepctr.make_feature_specs(FEATURES, VOCAB, DIM)
    coll = EmbeddingCollection(specs, mesh)
    meta = coll.model_meta(model_sign="sig-1")
    assert len(meta.variables) == 6  # 3 features x (emb + linear)
    assert [v.variable_id for v in meta.variables] == list(range(6))
    with pytest.raises(ValueError, match="duplicate"):
        EmbeddingCollection(list(specs) + [specs[0]], mesh)


def test_auc_lift_on_learnable_task(devices8):
    """Eval path proves learning: AUC rises well above chance on a task the
    model can memorize (VERDICT: loss-decrease checks alone are weak)."""
    import optax
    from openembedding_tpu import EmbeddingCollection, EmbeddingSpec, Trainer
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.parallel.mesh import create_mesh
    from openembedding_tpu.utils.observability import StreamingAUC

    mesh = create_mesh(2, 4, devices8)
    specs = (
        EmbeddingSpec(name="f", input_dim=256, output_dim=8,
                      optimizer={"category": "adagrad",
                                 "learning_rate": 0.5}),
        EmbeddingSpec(name="f:linear", input_dim=256, output_dim=1,
                      optimizer={"category": "adagrad",
                                 "learning_rate": 0.5}),
    )
    coll = EmbeddingCollection(specs, mesh)
    trainer = Trainer(deepctr.LogisticRegression(feature_names=("f",)),
                      coll, optax.adam(1e-2))
    rng = np.random.RandomState(0)

    def batch():
        ids = rng.randint(0, 256, 256).astype(np.int32)
        label = ((ids.astype(np.int64) * 2654435761) % 3 == 0).astype(np.float32)
        return {"label": label, "dense": None,
                "sparse": {"f": ids, "f:linear": ids}}

    state = trainer.init(jax.random.PRNGKey(0), trainer.shard_batch(batch()))
    auc0 = StreamingAUC()
    for _ in range(4):
        b = batch()
        auc0.update(b["label"], np.asarray(trainer.eval_step(state, b)))
    state, _ = trainer.fit(state, (batch() for _ in range(60)))
    auc1 = StreamingAUC()
    for _ in range(4):
        b = batch()
        auc1.update(b["label"], np.asarray(trainer.eval_step(state, b)))
    assert auc0.result() < 0.6, f"untrained AUC {auc0.result():.3f}"
    assert auc1.result() > 0.9, f"trained AUC {auc1.result():.3f}"


def test_fit_host_spans_share_their_steps_number(devices8):
    """Three steps of ``fit`` under span tracing: each ``step`` span holds
    one ``trainer.place_batch``, one ``trainer.dispatch`` and the
    ``trainer.bookkeeping`` on both sides of it, all with the step's
    number, and a ``trainer.next_batch`` lies between two steps."""
    from openembedding_tpu.analysis import scope

    trainer = build_trainer("deepfm", create_mesh(2, 4, devices8))
    batches = list(synthetic_batches(3))
    state = trainer.init(jax.random.PRNGKey(0),
                         trainer.shard_batch(batches[0]))
    scope.set_tracing(True)
    scope.reset()
    try:
        trainer.fit(state, batches)
        events = [e for e in scope.export_chrome_trace()["traceEvents"]
                  if e.get("ph") == "X"]
    finally:
        scope.set_tracing(None)
        scope.reset()

    def inside(outer, e):
        return outer["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= outer["ts"] + outer["dur"]

    steps = [e for e in events if e["name"] == "step"]
    assert [e["args"]["step"] for e in steps] == [0, 1, 2]
    for step in steps:
        held = [e for e in events
                if e["name"].startswith("trainer.") and inside(step, e)]
        assert [e["name"] for e in held] == [
            "trainer.bookkeeping", "trainer.place_batch",
            "trainer.dispatch", "trainer.bookkeeping"]
        assert {e["args"]["step"] for e in held} == {step["args"]["step"]}
    fetches = [e for e in events if e["name"] == "trainer.next_batch"]
    assert len(fetches) == 3 and not any(
        inside(step, e) for step in steps for e in fetches)
    for before, after in zip(steps, steps[1:]):
        assert any(before["ts"] + before["dur"] <= e["ts"]
                   and e["ts"] + e["dur"] <= after["ts"] for e in fetches)

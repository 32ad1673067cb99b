"""Three ``Trainer`` steps over one fused hash table whose finds walk
chunks leave what the find that took every key of a call in one pass a
level left, bit for bit, on one device and routed over 2x2, for int32 and
wide keys."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from openembedding_tpu import hash_table as ht
from openembedding_tpu.parallel.mesh import create_mesh

DIM = 4


def _one_pass_find_levels(table_keys, query, valid, max_probes):
    """The find as it was before it walked chunks, over every key of the
    call in one pass a level: the reference of the test below."""
    capacity = table_keys.shape[0]
    bsz, _nb, chain = ht.table_layout(capacity, max_probes)
    b0 = ht.probe_starts(query, capacity, max_probes) // bsz

    def level(j, slot):
        match, _ = ht._bucket_masks(table_keys, query, b0 + j, max_probes)
        hit = valid & (slot < 0) & jnp.any(match, axis=1)
        first = jnp.argmax(match, axis=1).astype(jnp.int32)
        return jnp.where(hit, (b0 + j) * bsz + first, slot)

    n = query.shape[0]
    return (jax.lax.fori_loop(0, chain, level,
                              jnp.full((n,), -1, jnp.int32)), jnp.int32(n))


@pytest.mark.parametrize("key_dtype", ["int32", "wide"])
@pytest.mark.parametrize("data,model", [(1, 1), (2, 2)],
                         ids=["1x1", "2x2"])
def test_three_trainer_steps_equal_the_one_pass_find(monkeypatch, devices8,
                                                     data, model, key_dtype):
    """DeepFM over one fused hash table, three steps of ``Trainer``: every
    leaf of the tables and of the dense model is what the one-pass find
    leaves, bit for bit, with pushes several chunks wide."""
    import optax
    from openembedding_tpu import EmbeddingCollection, Trainer
    from openembedding_tpu.fused import make_fused_specs
    from openembedding_tpu.models import deepctr
    features, batch = ("a", "b", "c", "d"), 1024
    mesh = create_mesh(data, model, devices8[:data * model])
    rng = np.random.RandomState(31)
    raw = [{"label": (rng.rand(batch) > 0.5).astype(np.float32),
            "dense": rng.randn(batch, 4).astype(np.float32),
            "sparse": {f: (rng.zipf(1.2, batch) % 50021).astype(np.int32)
                       for f in features}} for _ in range(3)]

    def three_steps():
        jax.clear_caches()      # the push programs are cached by their spec
        specs, mapper = make_fused_specs(
            features, -1, DIM, hash_capacity=1 << 14, key_dtype=key_dtype,
            optimizer={"category": "adagrad", "learning_rate": 0.1})
        trainer = Trainer(deepctr.build_model("deepfm", features),
                          EmbeddingCollection(specs, mesh), optax.adam(1e-2))
        batches = [mapper.fuse_batch(b) for b in raw]
        state = trainer.init(jax.random.PRNGKey(0),
                             trainer.shard_batch(batches[0]))
        for b in batches:
            state, _ = trainer.train_step(state, b)
        return jax.device_get((state.emb, state.params))

    def traced(find, widths):
        def spy(table_keys, query, *rest):
            widths.append(query.shape[0])
            return find(table_keys, query, *rest)
        return spy

    chunked, one_pass = [], []
    monkeypatch.setattr(ht.table_lib, "FIND_CHUNK", 256)
    monkeypatch.setattr(ht, "_find_levels", traced(ht._find_levels, chunked))
    got = three_steps()
    monkeypatch.setattr(ht, "_find_levels",
                        traced(_one_pass_find_levels, one_pass))
    want = three_steps()
    # each form was traced into its own steps, over several chunks a push
    assert chunked == one_pass and min(chunked) >= 3 * 256, (chunked,
                                                             one_pass)
    assert int(want[0]["fields"].num_used()) > 300
    assert int(want[0]["fields"].insert_failures) == 0
    jax.tree.map(np.testing.assert_array_equal, got, want)

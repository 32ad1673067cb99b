"""Worker for the int64-hash-key test: runs with jax_enable_x64.

The reference's hash key space is 2^62 (tf.strings.to_hash_bucket_fast into
int64, exb.py input_dim=-1 -> 2^63 vocab). int64 keys need the global x64
flag, which changes dtypes program-wide — hence a dedicated process (the
documented deployment shape for full-width key spaces).
"""

import os
import sys


def main() -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)
    jax.config.update("jax_enable_x64", True)

    import numpy as np
    import jax.numpy as jnp
    from openembedding_tpu import EmbeddingCollection, EmbeddingSpec
    from openembedding_tpu import checkpoint as ckpt
    from openembedding_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(2, 2)
    spec = EmbeddingSpec(name="h", input_dim=-1, output_dim=4,
                         hash_capacity=1024, key_dtype="int64",
                         initializer={"category": "constant", "value": 0.5},
                         optimizer={"category": "sgd", "learning_rate": 1.0})
    coll = EmbeddingCollection((spec,), mesh)
    states = coll.init(jax.random.PRNGKey(0))

    # keys far beyond int32 range: distinct keys that would collide if
    # anything truncated to 32 bits
    base = np.int64(1) << 40
    keys = np.asarray([base + 1, base + 2, (np.int64(1) << 45) + 1,
                       base + 1], np.int64)
    jk = jnp.asarray(keys)
    rows = coll.pull(states, {"h": jk}, batch_sharded=True)["h"]
    np.testing.assert_allclose(np.asarray(rows), 0.5, rtol=1e-6)
    g = jnp.ones((4, 4), jnp.float32)
    states = coll.apply_gradients(states, {"h": jk}, {"h": g})
    assert int(states["h"].insert_failures) == 0
    rows = np.asarray(coll.pull(states, {"h": jk},
                                batch_sharded=True)["h"])
    # duplicate key (rows 0 and 3) got grad sum 2; distinct keys 1 each
    np.testing.assert_allclose(rows[0], 0.5 - 2.0, rtol=1e-6)
    np.testing.assert_allclose(rows[1], 0.5 - 1.0, rtol=1e-6)
    np.testing.assert_allclose(rows[2], 0.5 - 1.0, rtol=1e-6)
    np.testing.assert_allclose(rows[3], rows[0], rtol=1e-6)
    # 3 distinct rows materialized (no 32-bit aliasing)
    assert int(jax.device_get(states["h"].num_used())) == 3

    # checkpoint round trip preserves 64-bit keys
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        ckpt.save_checkpoint(d, coll, states)
        loaded = ckpt.load_checkpoint(d, coll)
        got = np.asarray(coll.pull(loaded, {"h": jk},
                                   batch_sharded=True)["h"])
        np.testing.assert_allclose(got, rows, rtol=1e-6)

    print("x64 worker: ok", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

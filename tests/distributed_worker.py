"""Worker body for the 2-process distributed test (run by test_distributed).

Each process is one "host" with 2 virtual CPU devices; the 2x2 global mesh
spans both. This is the JAX-native version of the reference's fork-based
multi-node simulation (core::MultiProcess, entry/c_api_test.h:194): real
cross-process collectives, one box.
"""

import os
import sys


def main() -> int:
    rank = int(sys.argv[1])
    port = sys.argv[2]
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    from openembedding_tpu import distributed
    distributed.initialize(master_endpoint=f"127.0.0.1:{port}",
                           num_workers=2, worker_rank=rank)
    assert distributed.num_workers() == 2
    assert distributed.worker_rank() == rank
    assert len(jax.devices()) == 4, jax.devices()
    assert len(jax.local_devices()) == 2

    import numpy as np
    import jax.numpy as jnp
    from openembedding_tpu import EmbeddingCollection, EmbeddingSpec

    # reference Communication parity: barrier + broadcast
    distributed.barrier("boot")
    v = distributed.broadcast(np.asarray([123.0 + rank], np.float32))
    assert float(v[0]) == 123.0, v  # rank 0's value everywhere

    mesh = distributed.create_global_mesh(data=2, model=2)
    spec = EmbeddingSpec(name="t", input_dim=32, output_dim=4,
                         initializer={"category": "constant", "value": 0.0},
                         optimizer={"category": "sgd", "learning_rate": 1.0})
    coll = EmbeddingCollection((spec,), mesh)
    states = coll.init(jax.random.PRNGKey(0))

    # each process contributes ITS OWN batch slice: 4 rows each, global 8.
    # Every entry hits row 5 with grad 1.0 -> after one step w[5] = -8
    # only if gradients crossed the process boundary.
    local_ids = np.full((4,), 5, np.int32)
    gbatch = distributed.local_batch_to_global(
        {"t": local_ids}, mesh)
    rows = coll.pull(states, gbatch)
    assert rows["t"].shape == (8, 4)
    g = jnp.ones_like(rows["t"])
    states = coll.apply_gradients(states, gbatch, {"t": g})

    from jax.experimental import multihost_utils
    probe = distributed.local_batch_to_global(
        {"t": np.asarray([5, 6], np.int32) if rank == 0
         else np.asarray([5, 7], np.int32)}, mesh)
    out = coll.pull(states, probe)["t"]
    full = np.asarray(multihost_utils.process_allgather(out, tiled=True))
    # global probe order: rank0 ids [5, 6] then rank1 ids [5, 7]
    np.testing.assert_allclose(full[:, 0], [-8.0, 0.0, -8.0, 0.0],
                               rtol=1e-6, atol=1e-6)

    # multi-host checkpoint: each process writes its part files; reload on
    # the same cluster reproduces the table (per-node dump layout)
    if len(sys.argv) > 3:
        from openembedding_tpu import checkpoint as ckpt
        ckpt_dir = sys.argv[3]
        hspec = EmbeddingSpec(name="h", input_dim=-1, output_dim=4,
                              hash_capacity=256,
                              initializer={"category": "constant",
                                           "value": 0.25},
                              optimizer={"category": "sgd",
                                         "learning_rate": 1.0})
        coll2 = EmbeddingCollection((spec, hspec), mesh)
        st2 = coll2.init(jax.random.PRNGKey(0))
        st2["t"] = states["t"]  # the trained table from above
        hkeys = distributed.local_batch_to_global(
            {"h": np.asarray([1001, 1002], np.int32) if rank == 0
             else np.asarray([1003, 1004], np.int32)}, mesh)
        st2 = coll2.apply_gradients(
            st2, hkeys, {"h": jnp.ones((4, 4), jnp.float32)})
        ckpt.save_checkpoint(ckpt_dir, coll2, st2, model_sign="mh-1")
        loaded = ckpt.load_checkpoint(ckpt_dir, coll2)
        got = coll2.pull(loaded, probe)["t"]
        lfull = np.asarray(multihost_utils.process_allgather(
            got, tiled=True))
        np.testing.assert_allclose(lfull, full, rtol=1e-6, atol=1e-6)
        hprobe = distributed.local_batch_to_global(
            {"h": np.asarray([1001, 1003], np.int32) if rank == 0
             else np.asarray([1004, 9999], np.int32)}, mesh)
        hrows = np.asarray(multihost_utils.process_allgather(
            coll2.pull(loaded, hprobe, read_only=True)["h"], tiled=True))
        np.testing.assert_allclose(hrows[:3], 0.25 - 1.0, rtol=1e-6)
        np.testing.assert_allclose(hrows[3], 0.0)  # unseen key
        print(f"worker {rank}: multihost checkpoint ok", flush=True)

    distributed.barrier("done")
    print(f"worker {rank}: ok", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

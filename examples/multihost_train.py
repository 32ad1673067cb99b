"""Multi-host training launch — the reference's MPI/network examples.

The TPU-native counterpart of
/root/reference/examples/criteo_deepctr_network_mpi.py (MPI ranks build the
cluster, each worker feeds its own data shard):

TPU pod (one command per host; the pod runtime supplies topology):

    python examples/multihost_train.py

CPU/GPU cluster or local 2-process demo (reference-style explicit flags):

    python examples/multihost_train.py --master 127.0.0.1:9911 \
        --num_workers 2 --worker_rank 0 &
    python examples/multihost_train.py --master 127.0.0.1:9911 \
        --num_workers 2 --worker_rank 1

Each process contributes its own batch shard (``local_batch_to_global``);
the (data, model) mesh spans every host's devices and the same SPMD train
step runs everywhere.
"""

import argparse
import sys


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--master", default=None,
                   help="coordinator ip:port (None = TPU pod auto-detect)")
    p.add_argument("--num_workers", type=int, default=None)
    p.add_argument("--worker_rank", type=int, default=None)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch_per_host", type=int, default=256)
    p.add_argument("--data_axis", type=int, default=0,
                   help="0 = one data row per process")
    args = p.parse_args(argv)

    import numpy as np
    import jax
    import optax

    from openembedding_tpu import (EmbeddingCollection, Trainer, distributed)
    from openembedding_tpu.fused import make_fused_specs
    from openembedding_tpu.models import deepctr

    distributed.initialize(args.master, args.num_workers, args.worker_rank)
    rank = distributed.worker_rank()
    print(f"worker {rank}/{distributed.num_workers()}: "
          f"{len(jax.local_devices())} local / {len(jax.devices())} global "
          "devices", flush=True)

    data_axis = args.data_axis or distributed.num_workers()
    mesh = distributed.create_global_mesh(data=data_axis)
    features = tuple(f"c{i}" for i in range(8))
    specs, mapper = make_fused_specs(features, 1 << 16, 8)
    coll = EmbeddingCollection(specs, mesh)
    trainer = Trainer(deepctr.build_model("deepfm", features), coll,
                      optax.adagrad(0.05))
    rng = np.random.RandomState(rank)  # each host reads ITS OWN shard

    def host_batch():
        b = args.batch_per_host
        sparse = {f: rng.randint(0, 1 << 16, b).astype(np.int32)
                  for f in features}
        return mapper.fuse_batch({
            "label": (rng.rand(b) > 0.5).astype(np.float32),
            "dense": rng.randn(b, 13).astype(np.float32),
            "sparse": sparse})

    def global_batch():
        return distributed.local_batch_to_global(host_batch(), mesh)

    state = trainer.init(jax.random.PRNGKey(0), global_batch())
    for i in range(args.steps):
        # batches are already globally sharded; shard_batch is a no-op on
        # arrays that carry the right sharding
        state, m = trainer.train_step(state, global_batch())
        if rank == 0 and (i + 1) % 5 == 0:
            print(f"step {i + 1}: loss={float(m['loss']):.5f}", flush=True)
    distributed.barrier("done")
    if rank == 0:
        print("multihost training done", flush=True)
    return 0


if __name__ == "__main__":
    # the script entry, not main(): tests call main() in-process and
    # compile cold
    from openembedding_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())

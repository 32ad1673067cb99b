"""End-to-end serving demo: train -> checkpoint -> HA replicas -> lookups.

The TPU-native counterpart of the reference's serving examples
(/root/reference/examples/tensorflow_serving_restful.py — curl against
TF-Serving — plus the controller cluster of documents/en/serving.md):

    python examples/serving_cluster.py --replicas 2 --steps 20
    python examples/serving_cluster.py --shards 2 --replicas 2   # 2x2 grid

trains a small DeepFM, saves a version-stamped checkpoint, boots N replica
daemons (one loads the model, the rest restore the catalog from a living
peer), then issues lookups through the failover router and prints the
cluster's liveness and /metrics endpoints. Kill a replica while it runs to
watch the router ride through (the chaos test automates exactly that).
``--shards G`` demonstrates SHARD-GROUP serving for models larger than one
process: G groups x --replicas processes each load only ids = k (mod G),
and a ShardedRoutingClient fans lookups to owners and merges rows — the
reference's shard x replica placement (client/Model.cpp:153-186).
"""

import argparse
import sys
import tempfile
import time


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--shards", type=int, default=1,
                   help=">1: shard-group serving (each process holds a "
                        "1/G slice of every table)")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--lookups", type=int, default=5)
    p.add_argument("--compress", default="",
                   help="binary data-plane codec ('' | zlib): replicas "
                        "compress lookup responses for advertising "
                        "clients and peer-restore row pages (the "
                        "reference's server.message_compress)")
    args = p.parse_args(argv)
    from openembedding_tpu.utils import compress as compress_lib
    compress_lib.check(args.compress)   # fail at parse time, not after
                                        # replicas spawn + 300s waits

    import numpy as np
    import jax
    import optax

    from openembedding_tpu import (EmbeddingCollection, Trainer,
                                   checkpoint as ckpt)
    from openembedding_tpu.fused import make_fused_specs
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.parallel.mesh import create_mesh
    from openembedding_tpu.serving import ha

    # --- train + save ------------------------------------------------------
    mesh = create_mesh(1, len(jax.devices()))
    features = tuple(f"c{i}" for i in range(8))
    specs, mapper = make_fused_specs(features, 4096, 8)
    coll = EmbeddingCollection(specs, mesh)
    trainer = Trainer(deepctr.build_model("deepfm", features), coll,
                      optax.adagrad(0.05))
    rng = np.random.RandomState(0)

    def batch():
        sparse = {f: rng.randint(0, 4096, 256).astype(np.int32)
                  for f in features}
        return mapper.fuse_batch({
            "label": (rng.rand(256) > 0.5).astype(np.float32),
            "dense": rng.randn(256, 13).astype(np.float32),
            "sparse": sparse})

    state = trainer.init(jax.random.PRNGKey(0), trainer.shard_batch(batch()))
    state, _ = trainer.fit(state, (batch() for _ in range(args.steps)))
    sign = trainer.model_sign(state)
    model_dir = tempfile.mkdtemp(prefix="oe_serving_demo_")
    ckpt.save_checkpoint(model_dir, coll, state.emb, model_sign=sign)
    print(f"saved {sign} -> {model_dir}")

    # --- replica cluster ---------------------------------------------------
    import socket

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    # this process trained above and still holds its devices; replicas
    # are CPU children (ha.spawn_replica) — one process for each chip
    if args.shards > 1:
        # shard groups x replicas: every process loads its slice directly
        groups = [[free_port() for _ in range(args.replicas)]
                  for _ in range(args.shards)]
        eps = [[f"127.0.0.1:{pt}" for pt in row] for row in groups]
        procs = []
        for k, row in enumerate(groups):
            for pt in row:
                procs.append(ha.spawn_replica(
                    pt, load=[f"{sign}={model_dir}"],
                    shard_index=k, shard_count=args.shards,
                    compress=args.compress))
        for i, ep in enumerate(ep for row in eps for ep in row):
            if not ha.wait_ready(ep, sign=sign, timeout=300.0):
                pr = procs[i]
                pr.kill()
                out = (pr.stdout.read() or "") if pr.stdout else ""
                for other in procs:   # no orphaned daemons on failure
                    other.kill()
                raise AssertionError(
                    f"replica {ep} failed; last output:\n"
                    + "\n".join(out.splitlines()[-15:]))
        print(f"shard-group cluster up: {eps}")
        flat_eps = [ep for row in eps for ep in row]
    else:
        ports = [free_port() for _ in range(args.replicas)]
        flat_eps = eps = [f"127.0.0.1:{pt}" for pt in ports]
        procs = [ha.spawn_replica(ports[0], load=[f"{sign}={model_dir}"],
                                  compress=args.compress)]
        assert ha.wait_ready(eps[0], sign=sign, timeout=300.0), "first replica failed"
        for pt in ports[1:]:
            procs.append(ha.spawn_replica(pt, peers=[eps[0]],
                                          compress=args.compress))
        for ep in eps[1:]:
            assert ha.wait_ready(ep, sign=sign, timeout=300.0), f"replica {ep} failed"
        print(f"cluster up: {eps}")

    try:
        router = (ha.ShardedRoutingClient(eps, compress=args.compress)
                  if args.shards > 1
                  else ha.RoutingClient(eps, compress=args.compress))
        for n in router.nodes():
            print(f"  node {n['endpoint']}: alive={n['alive']} "
                  f"models={n['models']}")
        ids = np.arange(8, dtype=np.int64)
        for _ in range(args.lookups):
            rows = router.lookup(sign, "fields", ids)
            print(f"lookup fields[0:8] -> shape {rows.shape}, "
                  f"|row0|={np.abs(rows[0]).sum():.4f}")
            time.sleep(0.2)
        ep0 = flat_eps[0]
        print(f"metrics: curl http://{ep0}/metrics")
        print(f"cluster: curl http://"
              f"{flat_eps[1] if len(flat_eps) > 1 else ep0}/cluster")
    finally:
        for pr in procs:
            pr.kill()
    print("done")
    return 0


if __name__ == "__main__":
    # the script entry, not main(): tests call main() in-process and
    # compile cold
    from openembedding_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())

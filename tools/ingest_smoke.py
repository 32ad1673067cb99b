"""Streaming-ingest CI smoke: shards -> reader pool -> train steps, with
the zero-post-warmup-stall contract ASSERTED.

    python -m tools.ingest_smoke --out /tmp/ingest_smoke.json

Generates a small synthetic shard set (real TSV files, zipf marginals,
hex categoricals — ``data.stream.write_synthetic_shards``), streams it
through the parallel reader pool into an a2a-plane deepfm Trainer
for ``--steps`` steps on the virtual CPU mesh, and exits nonzero
unless:

* post-warmup ingest stalls are ZERO (every measured pop found its
  batch ready — the stream records literal 0.0 for ready pops, so the
  assertion is exact, not a histogram approximation);
* no rows were dropped as bad and no reader died;
* the ingest spans (``ingest.read`` / ``ingest.hash``) actually
  recorded — a silent instrumentation regression must fail the smoke,
  not pass it vacuously (the graftscope span-coverage contract).

Writes a one-line JSON summary to ``--out`` for the CI artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--shard-rows", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--readers", type=int, default=2)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", args.devices)

    import optax
    from openembedding_tpu import EmbeddingCollection, Trainer
    from openembedding_tpu.analysis import scope
    from openembedding_tpu.data import criteo, stream
    from openembedding_tpu.fused import make_fused_specs
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.parallel.mesh import create_mesh

    n_dev = len(jax.devices())
    mesh = create_mesh(2 if n_dev % 2 == 0 else 1,
                       n_dev // (2 if n_dev % 2 == 0 else 1))
    shard_dir = tempfile.mkdtemp(prefix="ingest_smoke_")
    problems = []
    summary = {}
    try:
        stream.write_synthetic_shards(shard_dir, num_shards=args.shards,
                                      rows_per_shard=args.shard_rows,
                                      fmt="tsv", seed=0)
        specs, mapper = make_fused_specs(
            tuple(criteo.SPARSE_NAMES), 1 << 14, 8,
            optimizer={"category": "adagrad", "learning_rate": 0.01},
            plane="a2a")
        coll = EmbeddingCollection(specs, mesh)
        trainer = Trainer(deepctr.build_model(
            "deepfm", tuple(criteo.SPARSE_NAMES)), coll,
            optax.adagrad(0.01))
        src = stream.ShardStream(shard_dir, batch_size=args.batch,
                                 readers=args.readers, epochs=None,
                                 num_buckets=1 << 14,
                                 transform=mapper.fuse_batch,
                                 name="smoke")
        try:
            it = iter(src)
            cur = next(it)
            state = trainer.init(jax.random.PRNGKey(0),
                                 trainer.shard_batch(cur))
            t0 = time.perf_counter()
            for i in range(args.steps):
                nxt = next(it)
                state, m = trainer.train_step(state, cur,
                                              next_batch=nxt)
                cur = nxt
                if i + 1 == args.warmup:
                    jax.block_until_ready(m["loss"])
                    src.reset_stall_stats()
            jax.block_until_ready(m["loss"])
            dt = time.perf_counter() - t0
            stalls = src.stall_summary()
            mem = src.memory_stats()
            summary = {
                "steps": args.steps,
                "eps": round(args.steps * args.batch / dt, 1),
                "stall_p95_ms": stalls["p95_ms"],
                "stall_max_ms": stalls["max_ms"],
                "stalled_pops": stalls["stalled"],
                "measured_pops": stalls["pops"],
                "bad_rows": int(src.bad_rows()),
                "rows_read": int(mem["rows_read"]),
                "ring_capacity_batches":
                    int(mem["ring_capacity_batches"]),
                "read_spans": scope.HISTOGRAMS.count(
                    "span_ingest_read_seconds", stream="smoke",
                    fmt="tsv"),
                "hash_spans": scope.HISTOGRAMS.count(
                    "span_ingest_hash_seconds", stream="smoke"),
            }
            if stalls["stalled"] or stalls["max_ms"] > 0.0:
                problems.append(
                    f"{stalls['stalled']} post-warmup stall(s), max "
                    f"{stalls['max_ms']:.3f} ms — the ring fell behind "
                    "the step rate")
            if src.bad_rows():
                problems.append(f"{src.bad_rows()} bad row(s) in a "
                                "clean synthetic shard set")
            if not summary["read_spans"] or not summary["hash_spans"]:
                problems.append("ingest.read/ingest.hash spans missing "
                                "— instrumentation regression")
        finally:
            src.close()
    finally:
        shutil.rmtree(shard_dir, ignore_errors=True)

    summary["problems"] = problems
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=2)
    if problems:
        for p in problems:
            print(f"ingest_smoke: {p}", file=sys.stderr)
        print("ingest_smoke: FAILED", file=sys.stderr)
        return 1
    print("ingest_smoke: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())

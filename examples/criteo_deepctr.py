"""Criteo CTR training driver — the reference benchmark's CLI equivalent.

Mirrors /root/reference/test/benchmark/criteo_deepctr.py (flags --model
WDL/DeepFM/xDeepFM, --data csv/TSV, --batch_size, --save/--load, --optimizer)
and the examples/criteo_deepctr_network*.py flows, on the TPU-native stack:

    python examples/criteo_deepctr.py --model deepfm --steps 200
    python examples/criteo_deepctr.py --data train.tsv --format tsv
    python examples/criteo_deepctr.py --save /tmp/ckpt --steps 100
    python examples/criteo_deepctr.py --load /tmp/ckpt --eval_steps 50

Defaults run on synthetic zipfian Criteo-shaped data so the example is
self-contained (the reference ships train100.csv for the same reason).
"""

import argparse
import itertools
import sys
import time

import numpy as np


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="deepfm",
                   choices=["lr", "wdl", "deepfm", "xdeepfm", "dcn"])
    p.add_argument("--data", default="", help="path to criteo csv/tsv; "
                   "empty = synthetic stream")
    p.add_argument("--format", default="csv",
                   choices=["csv", "tsv", "tfrecord"])
    p.add_argument("--readers", type=int, default=0, metavar="N",
                   help="stream --data through the parallel shard "
                        "reader pool (data/stream.py: N reader "
                        "threads, bounded prefetch ring, worker-side "
                        "hashing, per-step stall accounting). --data "
                        "may be a shard DIRECTORY (*.tsv / tf-part.*) "
                        "or one file; tsv/tfrecord only. 0 = the "
                        "single-threaded portable readers")
    p.add_argument("--batch_size", type=int, default=4096)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--eval_steps", type=int, default=0)
    p.add_argument("--embedding_dim", type=int, default=9)
    p.add_argument("--num_buckets", type=int, default=1 << 22,
                   help="hashed id space per the TSV path")
    p.add_argument("--optimizer", default="adagrad")
    p.add_argument("--learning_rate", type=float, default=0.01)
    p.add_argument("--dense_lr", type=float, default=1e-3)
    p.add_argument("--fused", action="store_true", default=True,
                   help="fuse the 26 features into one table (default)")
    p.add_argument("--no-fused", dest="fused", action="store_false")
    p.add_argument("--hash", action="store_true",
                   help="unbounded hash tables instead of bounded buckets")
    p.add_argument("--sparse_as_dense", type=int, default=0, metavar="N",
                   help="keep embeddings with vocab <= N as dense data-"
                   "parallel params (the reference's --cache hybrid, "
                   "exb.py:617-632); needs --no-fused")
    p.add_argument("--plane", default="a2a",
                   choices=["a2a", "psum", "a2a+cache", "a2a+grouped",
                            # compressed-exchange rungs (precision.py):
                            # bf16 wire rows / bf16 pull + int8
                            # error-feedback push
                            "a2a+bf16", "a2a+int8",
                            "a2a+grouped+bf16"],
                   help="sparse data plane: owner-routed all-to-all "
                   "(default), the psum/all_gather baseline, a2a plus "
                   "the hot-row replica cache (parallel/hot_cache.py), "
                   "or the collection-batched grouped exchange — one "
                   "routed round per same-shape table group per step "
                   "(parallel/grouped.py; pair with --no-fused, where "
                   "per-table pipelines are the cost being batched)")
    p.add_argument("--cache_k", type=int, default=0,
                   help="a2a+cache replica rows per variable (0 = default)")
    p.add_argument("--hist_len", type=int, default=0, metavar="L",
                   help="add a DIN-style variable-length behavior-history "
                   "feature (padded to L, mean-pooled; reference "
                   "RaggedTensor lookups). Synthetic data + --no-fused only")
    p.add_argument("--data_parallel", type=int, default=1,
                   help="mesh data-axis size")
    p.add_argument("--save", default="", help="checkpoint dir to write")
    p.add_argument("--save_compress", default="",
                   help="checkpoint block codec: '' | zlib | zstd-if-"
                        "installed (framed .npyz streams; Python loads "
                        "read them transparently — keep '' for dumps the "
                        "native mmap library serves)")
    p.add_argument("--load", default="", help="checkpoint dir to read")
    p.add_argument("--log_every", type=int, default=20)
    p.add_argument("--retrace_budget", type=int, default=16,
                   help="XLA compilations allowed after the two-step "
                        "warmup (first hot-cache refresh and offload "
                        "inserts legitimately compile a few programs); "
                        "a trip prints a RuntimeWarning. -1 disables "
                        "the guard (analysis/retrace.py)")
    p.add_argument("--config", default="",
                   help="EnvConfig JSON file (a2a bucket sizing, report "
                        "interval/gate; OE_* env vars overlay it)")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    from openembedding_tpu.utils import compress as compress_lib
    compress_lib.check(args.save_compress)  # typo'd codec must fail NOW,
                                            # not after the training run

    import jax
    import optax

    from openembedding_tpu import (EmbeddingCollection, Trainer,
                                   checkpoint as ckpt)
    from openembedding_tpu.analysis.retrace import RetraceGuard
    from openembedding_tpu.data import criteo
    from openembedding_tpu.fused import make_fused_specs
    from openembedding_tpu.models import deepctr
    from openembedding_tpu.parallel.mesh import create_mesh
    from openembedding_tpu.utils.observability import StreamingAUC, vtimer, GLOBAL

    from openembedding_tpu.utils.envconfig import EnvConfig
    env_cfg = EnvConfig.load(path=args.config or None)
    reporter = env_cfg.apply_report()
    # exchange sizing + the compressed-exchange precision rungs (the
    # EnvConfig `exchange` section / OE_EXCHANGE_* env vars). A --plane
    # precision suffix composes: matching rungs agree, a conflicting
    # combination raises inside EmbeddingSpec (_resolve_precision)
    a2a_kw = env_cfg.a2a.spec_kwargs()
    exch_kw = env_cfg.exchange.spec_kwargs()
    if exch_kw != {"exchange_precision": "f32", "push_precision": "f32"}:
        a2a_kw = dict(a2a_kw, **exch_kw)

    n_dev = len(jax.devices())
    mesh = create_mesh(args.data_parallel, n_dev // args.data_parallel)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"on {jax.devices()[0].platform}")

    features = criteo.SPARSE_NAMES
    vocab = -1 if args.hash else args.num_buckets
    opt_config = {"category": args.optimizer,
                  "learning_rate": args.learning_rate}

    if args.fused:
        if args.sparse_as_dense:
            print("--sparse_as_dense needs --no-fused (a fused group is one "
                  "big table); ignoring")
        specs, mapper = make_fused_specs(
            features, vocab, args.embedding_dim, optimizer=opt_config,
            hash_capacity=1 << 22, plane=args.plane,
            cache_k=args.cache_k, **a2a_kw)
        dense_specs = ()
    else:
        specs = deepctr.make_feature_specs(
            features, vocab, args.embedding_dim, optimizer=opt_config,
            hash_capacity=1 << 22, plane=args.plane,
            cache_k=args.cache_k, **a2a_kw)
        mapper = None
        if args.sparse_as_dense:
            from openembedding_tpu import split_sparse_dense
            specs, dense_specs = split_sparse_dense(
                specs, args.sparse_as_dense, batch_size=args.batch_size)
            print(f"sparse_as_dense: {len(dense_specs)} dense-kept, "
                  f"{len(specs)} sharded")
        else:
            dense_specs = ()
    hist = args.hist_len and not args.fused and not args.data
    if args.hist_len and not hist:
        print("--hist_len needs --no-fused and synthetic data; ignoring")
    if hist:
        from openembedding_tpu import EmbeddingSpec
        features = tuple(features) + ("hist",)
        specs = tuple(specs) + (
            EmbeddingSpec(name="hist", input_dim=vocab, output_dim=args.embedding_dim,
                          optimizer=opt_config, pooling="mean",
                          hash_capacity=1 << 22, plane=args.plane,
                          cache_k=args.cache_k),
            EmbeddingSpec(name="hist:linear", input_dim=vocab, output_dim=1,
                          optimizer=opt_config, pooling="sum",
                          hash_capacity=1 << 22, plane=args.plane,
                          cache_k=args.cache_k))
    coll = EmbeddingCollection(specs, mesh)
    model = deepctr.build_model(args.model, features)
    trainer = Trainer(model, coll, optax.adam(args.dense_lr),
                      sparse_as_dense=dense_specs or None)

    streams = []   # open ShardStreams; closed after each consuming loop

    def close_streams():
        while streams:
            streams.pop().close()

    def batches(limit):
        if args.data:
            if args.readers > 0 and args.format in ("tsv", "tfrecord"):
                # parallel shard reader pool: parse + hash on worker
                # threads, bounded ring, identity-stable batches (the
                # offload lookahead's contract), stall-accounted
                from openembedding_tpu.data import stream as stream_lib
                reader = stream_lib.ShardStream(
                    args.data, batch_size=args.batch_size,
                    fmt=args.format, num_buckets=args.num_buckets,
                    readers=args.readers,
                    add_linear=mapper is None,
                    transform=(mapper.fuse_batch if mapper is not None
                               else None))
                streams.append(reader)
                if limit:
                    return itertools.islice(reader, limit)
                return reader
            if args.format == "tsv":
                reader = criteo.read_criteo_tsv(
                    args.data, args.batch_size,
                    num_buckets=args.num_buckets, max_batches=limit)
            elif args.format == "tfrecord":
                # the reference's TFRecord benchmark layout
                # (test/benchmark/criteo_tfrecord.py), read without TF
                from openembedding_tpu.data import tfrecord
                reader = tfrecord.read_criteo_tfrecord(
                    args.data, args.batch_size)
                if limit:
                    reader = itertools.islice(reader, limit)
            else:
                reader = criteo.read_criteo_csv(args.data, args.batch_size,
                                                max_batches=limit)
        else:
            reader = criteo.synthetic_criteo(args.batch_size,
                                             num_buckets=args.num_buckets,
                                             num_batches=limit)
        if mapper is not None:
            return (mapper.fuse_batch(b) for b in reader)
        reader = criteo.add_linear_columns(reader)
        if hist:
            from openembedding_tpu import pad_id_for, pad_ragged
            pad = pad_id_for(coll.specs["hist"])  # EMPTY sentinel for --hash
            rng = np.random.RandomState(7)

            def with_hist(it):
                for b in it:
                    n = len(b["label"])
                    h = pad_ragged(
                        [rng.randint(0, max(args.num_buckets, 2),
                                     rng.randint(0, args.hist_len + 1))
                         for _ in range(n)], max_len=args.hist_len,
                        pad_id=pad)
                    b["sparse"] = {**b["sparse"], "hist": h,
                                   "hist:linear": h}
                    yield b
            reader = with_hist(reader)
        return reader

    it = iter(batches(args.steps + 1))
    first = next(it)
    state = trainer.init(jax.random.PRNGKey(0), trainer.shard_batch(first))
    if args.load:
        import os
        template = {"params": state.params, "opt_state": state.opt_state,
                    "step": state.step}
        if os.path.exists(f"{args.load}/{ckpt.DENSE_FILE}"):
            emb, dense = ckpt.load_checkpoint(args.load, coll,
                                              dense_state_template=template)
            state = state.replace(emb=emb, params=dense["params"],
                                  opt_state=dense["opt_state"],
                                  step=dense["step"])
        else:
            print("warning: checkpoint has no dense state; MLP weights stay "
                  "freshly initialized")
            state = state.replace(emb=ckpt.load_checkpoint(args.load, coll))
        print(f"loaded checkpoint from {args.load}")

    t0 = time.time()
    n = 0
    guard = None
    try:
        # chain, never list(it): materializing the tail up front would
        # defeat the streaming path (--readers) — the reader pool's
        # bounded ring only bounds host memory if the loop pulls lazily
        for i, b in enumerate(itertools.chain([first], it)):
            if i >= args.steps:
                break
            with vtimer("train_step"):
                state, m = trainer.train_step(state, b)
            n += 1
            if n == 2 and args.retrace_budget >= 0:
                # steady state starts after the two-step warmup (see
                # Trainer.fit): every later compile is a retrace —
                # budgeted so a shape wobble in the input pipeline shows
                # up in CI logs instead of as a silent 100x step-time
                # regression
                guard = RetraceGuard(budget=args.retrace_budget,
                                     name="criteo_deepctr loop",
                                     on_exceed="warn")
                guard.__enter__()
            if args.log_every and (i + 1) % args.log_every == 0:
                print(f"step {i+1}: loss={float(m['loss']):.5f}")
    finally:
        # warn mode: __exit__ never raises, so the finally is purely a
        # leak guard (an abandoned guard would count compiles forever)
        if guard is not None:
            guard.__exit__(None, None, None)
        close_streams()
    if guard is not None:
        print(f"retrace guard: {guard.compiles} post-warmup XLA "
              f"compilation(s) (budget {args.retrace_budget})")
    if n:
        jax.block_until_ready(m["loss"])
        dt = time.time() - t0
        print(f"trained {n} steps, {n * args.batch_size / dt:.0f} examples/s")

    if args.eval_steps:
        auc = StreamingAUC()
        try:
            for i, b in enumerate(batches(args.eval_steps)):
                scores = trainer.eval_step(state, b)
                auc.update(b["label"], np.asarray(scores))
        finally:
            close_streams()
        print(f"eval AUC over {args.eval_steps} batches: {auc.result():.4f}")

    if args.save:
        with vtimer("checkpoint_save"):
            ckpt.save_checkpoint(
                args.save, coll, state.emb,
                dense_state={"params": state.params,
                             "opt_state": state.opt_state,
                             "step": state.step},
                model_sign=trainer.model_sign(state),
                compress=args.save_compress)
        print(f"saved checkpoint to {args.save}")
    if reporter is not None:
        reporter.report()
        reporter.stop()
    return 0


if __name__ == "__main__":
    # the script entry, not main(): tests call main() in-process and
    # compile cold
    from openembedding_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
